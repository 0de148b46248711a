"""chip_smoke.py rehearsed on the CPU, and the start-up seams it leans on.

The script itself only runs on the TPU and grows no CPU switch; the
tests import its phases and run them at the ``probe`` widths on the
virtual CPU mesh — the whole train -> checkpoint -> paged-serve -> HTTP
path, on one device and on four — so a broken phase is found here and
not with chip time. What a CPU run can say stops at control flow: the
last line it would print is ``"ok": false``.
"""

import json
import os

import jax
import pytest

import chip_smoke
from kvedge_tpu.config.runtime_config import RuntimeConfig
from kvedge_tpu.runtime import boot as boot_mod
from kvedge_tpu.runtime.compilecache import enable_compile_cache

PROBE = chip_smoke.SmokeShape(
    model=("vocab = 512\nd_model = 128\nn_layers = 2\nn_heads = 4\n"
           "n_kv_heads = 2\nd_ff = 512\n"),
    vocab=512, seq=64, batch=4, steps=4, checkpoint_every=2,
    page_size=8, prompt=24, shared=16, n_new=6,
)


@pytest.mark.parametrize("chips", [1, 4])
def test_smoke_phases_rehearsed_on_cpu(chips, tmp_path, monkeypatch, capsys):
    """Every phase of the one- and four-chip smoke, on that many of the
    eight virtual devices (the runtime builds its mesh from
    ``jax.devices()``, so the test hands it a shorter list)."""
    devices = jax.devices()[:chips]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devices)
    ok, report = chip_smoke.run_phases(
        PROBE, chips=chips, platform="cpu", seed=0, workdir=str(tmp_path))
    out = capsys.readouterr().out
    assert ok, report.get("error") or out[-2000:]
    assert report["feeder"] == "TokenFeeder"
    phases = report["phases"]
    mesh = "{ data = 2, model = 2 }"
    wanted = (
        ["train[state]", "serve[auto]", "serve[gather]", "attention-op",
         "ssm-op", "delta-op"]
        if chips == 1 else
        ["reference-train[1 chip]", "reference-serve[1 chip]",
         "train[state]", f"serve[{mesh}]"])
    assert list(phases) == wanted
    losses = phases["train[state]"]["losses"]
    assert len(losses) == PROBE.steps and losses[-1] < losses[0]
    # Read from the text of the train step the payload lowered itself.
    step = phases["train[state]"]["train_step"]
    assert step["tpu_custom_calls"] == 0 and step["programs"] >= 1
    assert step["num_partitions"] == chips
    assert (step["arguments_split"] > 0) == (chips == 4)
    served = phases["serve[auto]" if chips == 1 else f"serve[{mesh}]"]
    assert served["requests"]["failed"] == 0
    assert served["requests"]["sent"] == served["requests"]["succeeded"]
    assert served["metrics"]["kvedge_serve_recoveries_total"] == 0
    assert served["windows_dispatched"] >= 1 and served["prefix_hits"] >= 1
    # Off the TPU the kernel is never what a decode window lowers to.
    assert served["decode_window"]["attention_path"] == "gather"
    if chips == 4:
        assert any(served["decode_window"]["collectives"].values())
        assert "model" in served["decode_window"]["pool_placement"]
        assert report["comparisons"]["losses"]["max_abs_diff"] \
            <= chip_smoke.LOSS_TOLERANCE
    else:
        assert "equal" in "".join(report["comparisons"].values())
    # The last line: the contract's keys, and never ok on a CPU backend.
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": chips}
    assert json.loads(chip_smoke.last_line(ok, device)) == {
        "ok": False, "device": device}
    assert json.loads(chip_smoke.last_line(
        True, device | {"platform": "tpu"}))["ok"] is True


def test_smoke_script_refuses_a_cpu_backend(capsys, monkeypatch):
    """``python chip_smoke.py`` where JAX finds no TPU: nothing runs,
    exit code 1, last line ``"ok": false`` with the device it found.
    Run with no option it asks libtpu for one chip, whatever the host
    holds."""
    monkeypatch.setattr(os, "environ", dict(os.environ))
    assert chip_smoke.main([]) == 1
    assert os.environ["TPU_VISIBLE_CHIPS"] == "0"
    last = capsys.readouterr().out.strip().splitlines()[-1]
    doc = json.loads(last)
    assert doc["ok"] is False and doc["device"]["platform"] == "cpu"


def test_smoke_fails_on_a_degraded_payload(tmp_path, capsys):
    """A payload that comes back ``ok=False`` fails the smoke with the
    payload's own error, not a degraded handle that looks started: here
    the train payload's corpus does not exist."""
    with chip_smoke.CompileMeter() as meter:
        smoke = chip_smoke.Smoke(PROBE, chips=8, platform="cpu", seed=0,
                                 workdir=str(tmp_path), meter=meter)
        with pytest.raises(chip_smoke.SmokeFailure, match="degraded"):
            chip_smoke.phase_train(smoke, state="state",
                                   mesh="{ data = 0, model = 1 }")


def test_the_delta_op_phase_holds_both_forms_to_the_recurrence(
        tmp_path, capsys, monkeypatch):
    """The phase alone, as the chip runs it: both forms of the
    delta-rule mixer within float32 rounding of the recurrence in
    float64, and a form that strays fails the smoke."""
    from kvedge_tpu.models import delta

    with chip_smoke.CompileMeter() as meter:
        smoke = chip_smoke.Smoke(PROBE, chips=1, platform="cpu", seed=0,
                                 workdir=str(tmp_path), meter=meter)
        chip_smoke.phase_delta_op(smoke)
        gaps = smoke.report["phases"]["delta-op"]["gaps"]
        assert set(gaps) == {"one-token", "chunk"}
        assert all(0 < gap <= 2e-5 for pair in gaps.values() for gap in pair)
        assert "delta op, 4 rows of 4 heads of 128 x 128" \
            in capsys.readouterr().out
        # the solve left out: the chunk form is no longer the recurrence
        monkeypatch.setattr(delta.jax.scipy.linalg, "solve_triangular",
                            lambda a, b, **kw: b)
        with pytest.raises(chip_smoke.SmokeFailure, match="delta-rule"):
            chip_smoke.phase_delta_op(smoke)


def test_the_delta_op_phase_holds_the_kernel_to_the_one_token_form(
        tmp_path, capsys, monkeypatch):
    """The phase's second half: the one-pass step kernel (interpreted
    here, compiled on the chip) against ``_one_token`` on the stacked
    state, and a kernel that writes a row it was not asked to fails the
    smoke."""
    from kvedge_tpu.ops import delta_step

    with chip_smoke.CompileMeter() as meter:
        smoke = chip_smoke.Smoke(PROBE, chips=1, platform="cpu", seed=0,
                                 workdir=str(tmp_path), meter=meter)
        chip_smoke.phase_delta_op(smoke)
        entry = smoke.report["phases"]["delta-op"]
        assert entry["untouched_moved"] == 0
        assert entry["elements"] == 3 * 4 * 128 * 128
        assert entry["state_rel_gap"] <= 2e-6 and entry["o_rel_gap"] <= 1e-5
        assert "delta op, kernel vs plain one-token form at 4 rows" \
            in capsys.readouterr().out
        real = delta_step.delta_step

        def every_row_live(state, layer, *now, **kw):
            return real(state, layer, *now[:-1], None, **kw)

        monkeypatch.setattr(delta_step, "delta_step", every_row_live)
        with pytest.raises(chip_smoke.SmokeFailure,
                           match="not asked to touch"):
            chip_smoke.phase_delta_op(smoke)


def test_boot_once_on_a_degraded_runtime_exits_nonzero(tmp_path):
    """``kvedge-runtime boot --once`` has no /status reader, only an
    exit code: a degraded check must fail the command. The
    long-running pod (once=False) still stays up degraded."""
    from kvedge_tpu.bootstrap.commands import CommandError, run_command

    config = tmp_path / "config.toml"
    config.write_text(
        f'[runtime]\nstate_dir = "{tmp_path / "state"}"\n'
        '[tpu]\nplatform = "tpu"\n'  # the CPU test backend is not a TPU
        '[status]\nport = 0\nbind = "127.0.0.1"\n'
        '[payload]\nkind = "devicecheck"\n')
    with pytest.raises(boot_mod.DegradedBoot, match="expected platform"):
        boot_mod.boot(str(config), once=True)
    with pytest.raises(CommandError, match="degraded"):
        run_command(("kvedge-runtime", "boot", "--once", "--config",
                     str(config)))
    handle = boot_mod.start_runtime(RuntimeConfig.parse(config.read_text()))
    try:
        assert not handle.check.ok  # up, degraded, debuggable
    finally:
        handle.shutdown()


def test_compile_cache_is_placed_from_outside_or_fixed(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set the helper leaves JAX's own
    setting alone; without it, the same in-checkout path every time."""
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert enable_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first, second = enable_compile_cache(), enable_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert first == second == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        with open(os.path.join(repo, ".gitignore")) as fh:
            assert ".jax_cache/" in fh.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
