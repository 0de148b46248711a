"""The main path's Pallas kernels, compiled by the TPU's own compiler.

Every other kernel test runs in the Pallas interpreter on the CPU,
which cannot see what Mosaic refuses: a slice off the tiling, too much
VMEM, a DMA that is not lane-aligned. libtpu compiles for a chip that
is described and not attached, so each kernel is lowered here at the
widths the product runs (the 209M shape chip_smoke.py drives, and the
``flagship`` preset) against a described ``v5e:2x2`` topology — no chip
time, about two seconds each. Nothing executes: a pass says the chip's
compiler accepts the kernel, not that its numbers are right (the
interpreter tests and chip_smoke.py's kernel-vs-gather comparison say
that).

The file sorts before ``test_cli.py`` on purpose: the tier-1 clock has
to reach it.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import functools
import re

import jax
import jax.numpy as jnp
import pytest
import numpy as np
from jax.sharding import (
    Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding,
)


@pytest.fixture(scope="module")
def v5e():
    """The four described chips of a v5e:2x2, persistent cache off.

    A program compiled for a described device lands in the persistent
    cache but cannot be read back without a chip (the next compile
    warns and compiles again), so the cache stays off around these.
    """
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu in this environment
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topo.devices
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


@pytest.fixture
def chip(v5e):
    return SingleDeviceSharding(v5e[0])


def _paged(*, batch=8, heads=16, kv=4, dh=64, page=128, max_pages=16,
           pool_pages=64, layers=2, int8=False, window=0, blocked=False):
    """(fn, arg shapes) for one paged_decode_attention geometry: the
    whole [L, P, page, K*Dh] pool and a traced layer index, as the
    layer loop hands them over; with ``window``, each row's first
    position beside them."""
    from kvedge_tpu.ops.paged_attention import paged_decode_attention

    pool_dtype = jnp.int8 if int8 else jnp.bfloat16
    pool = ((layers, pool_pages, page, kv * dh), pool_dtype)
    args = [((batch, heads, dh), jnp.bfloat16), pool, pool,
            ((batch, max_pages), jnp.int32), ((batch,), jnp.int32),
            ((), jnp.int32)]
    if window:
        def bound(q, pool_k, pool_v, tables, positions, layer, first):
            return paged_decode_attention(
                q, pool_k, pool_v, tables, positions, layer, first=first,
                window=window, blocked=blocked)

        return bound, args + [((batch,), jnp.int32)]
    if blocked:
        return functools.partial(paged_decode_attention, blocked=True), args
    if not int8:
        return paged_decode_attention, args
    scale = ((layers, pool_pages, page, kv), jnp.float32)

    def quantized(q, pk, pv, tables, pos, layer, sk, sv):
        return paged_decode_attention(q, pk, pv, tables, pos, layer,
                                      scale_k=sk, scale_v=sv)

    return quantized, args + [scale, scale]


def _flash_fwd_bwd():
    from kvedge_tpu.ops.attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    qkv = ((512, 2048, 64), jnp.bfloat16)
    return jax.grad(loss, argnums=(0, 1, 2)), [qkv, qkv, qkv]


def _rmsnorm_fwd():
    from kvedge_tpu.ops.rmsnorm import _rmsnorm_fwd_pallas

    fn = functools.partial(_rmsnorm_fwd_pallas, block_rows=512,
                           interpret=False)
    return fn, [((16384, 1024), jnp.bfloat16), ((1024,), jnp.float32)]


def _fused_xent_fwd_bwd():
    from kvedge_tpu.ops.xent import fused_xent

    def loss(x, emb, targets):
        return fused_xent(x, emb, targets).sum()

    return jax.grad(loss, argnums=(0, 1)), [
        ((4096, 1024), jnp.bfloat16), ((32000, 1024), jnp.float32),
        ((4096,), jnp.int32),
    ]


def _expert_walk(tokens=64):
    """The walk over the touched experts (ops/expert_walk.py) at the
    delta cell's published widths: 40 held experts of 4,096 x 2,560
    (u | g) and 1,280 x 4,096 in bf16, in stacked leaves of 4 layers,
    inside a scan over the layers that hands the kernel the leaves
    whole and the layer's index."""
    from kvedge_tpu.ops.expert_walk import expert_walk

    layers, held, d, f = 4, 40, 4096, 1280

    def run(x, gate_of, w_in, w_out, touched):
        def one_layer(x, layer):
            out = expert_walk(x, gate_of, w_in, w_out, layer, touched[layer],
                              gated=True, interpret=False)
            return x + out.astype(x.dtype), None
        return jax.lax.scan(one_layer, x,
                            jnp.arange(layers, dtype=jnp.int32))[0]

    return run, [
        ((tokens, d), jnp.bfloat16), ((tokens, held), jnp.float32),
        ((layers, held, d, 2 * f), jnp.bfloat16),
        ((layers, held, f, d), jnp.bfloat16), ((layers, held), jnp.bool_),
    ]


# 209M widths unless said: 16 query / 4 KV heads of 64, 128-token pages,
# a 2,048-token cap (16 pages per sequence), 8 rows.
_CASES = {
    "paged_decode_bf16_209m": lambda: _paged(),
    # 64 pages (4 slots x 16) x 128 rows, each row of 4 scales padded to
    # 128 fp32 lanes in VMEM, twice, is exactly _SCALE_VMEM_BUDGET: the
    # largest int8 pool "auto" routes to the kernel.
    "paged_decode_int8_209m_scales_at_budget": lambda: _paged(int8=True),
    # The 8,192-token cap, 64 pages per sequence, and the largest cap
    # whose score rows, V image and landing pads (two blocks of 8 pages,
    # K and V) decode_scratch_fits_vmem admits at this width: 142 pages.
    "paged_decode_bf16_cap8192": lambda: _paged(max_pages=64,
                                                pool_pages=512),
    "paged_decode_int8_cap8192": lambda: _paged(int8=True, max_pages=64),
    "paged_decode_bf16_scratch_at_budget": lambda: _paged(max_pages=142,
                                                          pool_pages=512),
    # The benchmark cell's kernel alone: 64 rows, 24 query / 2 KV heads
    # of 128, 24 pages a row, 768 pages, 16 layers.
    "paged_decode_bf16_cell": lambda: _paged(
        batch=64, heads=24, kv=2, dh=128, max_pages=24, pool_pages=768,
        layers=16),
    # The window cell's two kernels: 64 rows, 28 query / 4 KV heads of
    # 128 (a query group of 7, a pool 512 wide). A full layer's table is
    # 64 pages (8,192 positions: scores, V image and pads are 11.4 of the
    # 12 MB budget); a window layer's is a row's cap of 35 pages, with
    # the bound of 4,096 positions and each row's first position.
    "paged_decode_bf16_window_cell_full": lambda: _paged(
        batch=64, heads=28, kv=4, dh=128, max_pages=64, pool_pages=2816,
        layers=2),
    "paged_decode_bf16_window_cell_window": lambda: _paged(
        batch=64, heads=28, kv=4, dh=128, max_pages=35, pool_pages=2240,
        layers=6, window=4096),
    # The blocked form (the V pages in blocks, for a table whose V image
    # does not fit): the K-EXAONE cell's full layer, 64 rows of 64 query
    # / 8 KV heads of 128 over 64 pages of a 1,024-wide pool (2 MiB of
    # scores, 1 of weights, 2 of landing pads: blocks of 4 pages); the
    # window cell's full layer at its published 16,384 positions (28
    # heads, a 512-wide pool, 128 pages: ROADMAP R2 (d)); and, so that
    # the bound is compiled in this form too, a window layer's table.
    "paged_decode_bf16_blocked_exaone_full": lambda: _paged(
        batch=64, heads=64, kv=8, dh=128, max_pages=64, pool_pages=2816,
        layers=1, blocked=True),
    "paged_decode_bf16_blocked_window_cell_16384": lambda: _paged(
        batch=64, heads=28, kv=4, dh=128, max_pages=128, pool_pages=2816,
        layers=2, blocked=True),
    "paged_decode_bf16_blocked_bound": lambda: _paged(
        batch=64, heads=64, kv=8, dh=128, max_pages=72, pool_pages=512,
        layers=4, window=8192, blocked=True),
    # The K-EXAONE cell's window layers keep the whole form: a row's cap
    # of 4 pages, the bound of 128 positions.
    "paged_decode_bf16_exaone_window": lambda: _paged(
        batch=64, heads=64, kv=8, dh=128, max_pages=4, pool_pages=256,
        layers=4, window=128),
    # The flagship preset serves MHA: 8 KV heads of 64 (width 512).
    "paged_decode_bf16_flagship": lambda: _paged(heads=8, kv=8),
    # The delta cell's held experts at a decode batch's 64 rows and at
    # its shorter prefill tail's 32 positions.
    "expert_walk_delta_cell_64": _expert_walk,
    "expert_walk_delta_cell_32": lambda: _expert_walk(32),
    "flash_attention_fwd_bwd_t2048": _flash_fwd_bwd,
    "rmsnorm_fwd_16384x1024": _rmsnorm_fwd,
    "fused_xent_fwd_bwd_4096x32000": _fused_xent_fwd_bwd,
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_kernel_compiles_for_v5e(chip, case):
    fn, shapes = _CASES[case]()
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), (
        f"{case}: compiled for the chip without a Mosaic kernel in it"
    )


def test_the_walk_reads_the_experts_where_they_lie(chip):
    """The stacked leaves of 5.03 GB go into the kernel as they come:
    the program's temporaries hold no copy of them, nor of one layer's
    1.26 GB, nor of one expert's 31 MB (a product written the wrong way
    round once had the compiler copy a 3.75 GB leaf transposed: the
    last test but two of this file)."""
    fn, shapes = _expert_walk()
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 5.03e9
    assert memory.temp_size_in_bytes < 4 << 20, memory.temp_size_in_bytes
    makers = set(re.findall(
        r" = bf16\[4,40,(?:4096,2560|1280,4096)\]\{[^}]*\} ([\w-]+)\(", text))
    assert makers <= {"parameter", "get-tuple-element"}, makers


@pytest.mark.parametrize("pool_spec", [
    P(), P(None, None, None, "model"),
], ids=["replicated", "kv_heads_over_model"])
def test_mosaic_refuses_the_kernel_over_several_chips(v5e, pool_spec):
    """Why kvcache.settle_paged_attention sends every pool that spans
    more than one chip to the gather, a merely replicated one too: a
    jitted program over four devices cannot hold a Mosaic kernel,
    however its arguments are laid out (only a shard_map can)."""
    mesh = Mesh(np.array(v5e).reshape(2, 2), ("data", "model"))
    fn, shapes = _paged()
    specs = [P(), pool_spec, pool_spec, P(), P(), P()]
    args = [jax.ShapeDtypeStruct(shape, dtype,
                                 sharding=NamedSharding(mesh, spec))
            for (shape, dtype), spec in zip(shapes, specs)]
    with pytest.raises(NotImplementedError,
                       match="cannot be automatically partitioned"):
        jax.jit(fn).lower(*args)


# The benchmark's cell (benchmark/configs/starcoder2-3b.json): 16 layers
# of StarCoder2-3B's widths, 768 pages of 128 tokens, 64 slots of 24
# pages, and the product's window of 64 steps and prefill chunk of 64.
_CELL = dict(vocab=49152, d_model=3072, n_heads=24, n_kv_heads=2,
             n_layers=16, d_ff=12288, max_seq=3072)
_CELL_PAGES, _CELL_PAGE, _CELL_SLOTS = 768, 128, 64


def _cell_program(program: str, chip, monkeypatch):
    """The capped decode window or the prefill chunk, lowered at the
    cell's shapes from abstract arguments placed on the described chip.
    What "auto" and the interpret switch would ask the backend, which
    is the CPU here, is answered as on the chip: the kernel, compiled."""
    import kvedge_tpu.ops
    from kvedge_tpu.models import (
        TransformerConfig, init_params, kvcache, serving_params,
    )

    monkeypatch.setattr(kvedge_tpu.ops, "pallas_interpret", lambda: False)
    cfg = TransformerConfig(**_CELL, paged_attention="kernel")

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    # The tree as serve holds it: cast once at load.
    params = jax.tree_util.tree_map(
        lambda a: on_chip(a.shape, a.dtype),
        jax.eval_shape(lambda: serving_params(
            init_params(jax.random.PRNGKey(0), cfg), cfg)))
    pool = on_chip((cfg.n_layers, _CELL_PAGES, _CELL_PAGE,
                    cfg.kv_heads * cfg.d_head), jnp.bfloat16)
    state = kvcache.PagedState(
        pool_k=pool, pool_v=pool,
        tables=on_chip((_CELL_SLOTS, cfg.max_seq // _CELL_PAGE), jnp.int32),
        lengths=on_chip((_CELL_SLOTS,), jnp.int32))
    if program == "prefill":
        return params, kvcache._paged_prefill.lower(
            params, state, on_chip((64,), jnp.int32),
            on_chip((), jnp.int32), cfg, on_chip((), jnp.int32))

    def row(dtype):
        return on_chip((_CELL_SLOTS,), dtype)

    return params, kvcache._paged_decode_window_capped.lower(
        params, state, row(jnp.int32), cfg, 64, row(jnp.bool_),
        row(jnp.int32), row(jnp.int32))


def _pool_sized_operations(hlo: str, sizes: set) -> list:
    """Instructions of optimised HLO whose array result has one of
    ``sizes`` elements and that move data: everything but the plumbing
    (parameters, tuple elements, bitcasts) and the in-place scatters (a
    ``scatter``, or a fusion whose computation holds one)."""
    import re

    scatters = {
        name for name, body in re.findall(
            r"^%?([\w.\-]+) \([^\n]*\{\n(.*?)^\}", hlo, re.M | re.S)
        if " scatter(" in body}
    found = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(", line)
        if not m or not m.group(2):
            continue
        name, dims, opcode = m.groups()
        if np.prod([int(d) for d in dims.split(",")]) not in sizes:
            continue
        if opcode in ("parameter", "get-tuple-element", "bitcast", "scatter"):
            continue
        called = re.search(r"calls=%?([\w.\-]+)", line)
        if opcode == "fusion" and called and called.group(1) in scatters:
            continue
        found.append(f"{name} = {opcode} [{dims}]")
    return found


@pytest.mark.parametrize("program", ["decode_window", "prefill"])
def test_cell_programs_leave_the_pool_where_it_is(chip, monkeypatch,
                                                  program):
    """The pool stays where it is (kvcache._run_paged): compiled for the
    chip at the benchmark cell's shapes, neither the 64-step decode
    window nor the prefill chunk holds an operation whose result has a
    whole pool's or a layer's slab's elements, other than the scatters
    that write the new rows in place: no copy, reshape, dynamic-slice
    or dynamic-update-slice of 0.8 GB or 50 MB, which were 14 of a
    decode step's 23 ms (PERF.md section 5). And so no temporary of a
    pool's size, nor, since serve casts its weights once at load, of a
    weight's: what is left is the step's activations."""
    _, lowered = _cell_program(program, chip, monkeypatch)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    # The window attends through the Mosaic kernel, the prefill gathers.
    assert ("tpu_custom_call" in hlo) == (program == "decode_window")
    slab = _CELL_PAGES * _CELL_PAGE * 256
    moved = _pool_sized_operations(hlo, {slab, _CELL["n_layers"] * slab})
    assert not moved, f"{program} moves the pool about: {moved}"
    memory = compiled.memory_analysis()
    temporaries = memory.temp_size_in_bytes
    needs = (memory.argument_size_in_bytes + memory.output_size_in_bytes
             - memory.alias_size_in_bytes + temporaries)
    print(f"{program} at the cell's shapes: needs {needs / 1e9:.3f} GB, "
          f"{temporaries / 1e9:.3f} GB of it temporaries")
    assert temporaries < 2 * slab * 2 * 4, (
        f"{program}: {temporaries / 1e9:.2f} GB of temporaries is a "
        f"pool's size (0.8 GB) or a weight's copy (0.6 GB and up)")


@pytest.mark.parametrize("program", ["decode_window", "prefill"])
def test_cell_programs_cast_no_weight(chip, monkeypatch, program):
    """The weights are cast once, where serve loads them
    (transformer.serving_params): over the tree serve holds, the
    optimised program has no ``convert`` to the compute dtype whose
    result has a weight matrix's elements, one layer's or all layers'.
    Over float32 masters there are five, 13.9 of a prefill chunk's 19.8
    ms and 16.7 ms a decode window on the chip (PERF.md section 5)."""
    import re

    params, lowered = _cell_program(program, chip, monkeypatch)
    sizes = {a.size for a in params.values() if a.dtype == jnp.bfloat16}
    assert sizes, "serve holds no leaf in the compute dtype"
    sizes |= {n // _CELL["n_layers"] for n in sizes}
    cast = []
    for line in lowered.compile().as_text().splitlines():
        m = re.search(r"= bf16\[([\d,]+)\]\S* convert\(", line)
        if m and np.prod([int(d) for d in m.group(1).split(",")]) in sizes:
            cast.append(line.strip()[:120])
    assert not cast, f"{program} casts weights each time it runs: {cast}"


def _patterned_cell_program(program: str, chip, monkeypatch,
                            name: str = "granite-4.0-h-small.batchgen"):
    """A patterned block's cell (benchmark/configs/granite-4.0-h-small.json:
    one period of the patterned block at its published widths, 36 of 72
    experts held, 64 slots of recurrent state beside 1,536 pages of one
    attention layer, a window of 16 steps); the sizes made as the server
    makes them, from the file through ``model_of`` and ``[model]``."""
    import kvedge_tpu.ops
    from benchmark import cellspec
    from kvedge_tpu.config.runtime_config import RuntimeConfig
    from kvedge_tpu.models import hybrid, kvcache, moe, ssm
    from kvedge_tpu.runtime.workload import derive_model_config

    monkeypatch.setattr(kvedge_tpu.ops, "pallas_interpret", lambda: False)
    # jax.default_backend() is the CPU here; on the chip the decode
    # window's one-token form is the recurrent kind's kernel
    # (ssm.step_in_kernel, delta.step_in_kernel: both ask ssm._on_tpu).
    monkeypatch.setattr(ssm, "_on_tpu", lambda: True)
    # and the held experts' sum walks the touched experts where
    # moe.walks_touched's rule takes the walk at the cell's shapes
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    cell = cellspec.load_cell(name)
    payload = cell.config["payload"]
    one = jax.devices()[:1]
    with monkeypatch.context() as patch:
        patch.setattr(jax, "devices", lambda *a, **k: one)
        cfg, _ = derive_model_config(
            RuntimeConfig.from_mapping(
                cellspec.runtime_document(cell, "<dir>", "cpu")),
            seq=payload["seq"])
    import dataclasses

    cfg = dataclasses.replace(cfg, dtype="bfloat16",
                              paged_attention="kernel")
    slots, pages = payload["serving_slots"], payload["serving_pages"]
    page = payload["serving_page_size"]

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def abstract(make):
        return jax.tree_util.tree_map(
            lambda a: on_chip(a.shape, a.dtype), jax.eval_shape(make))

    params = abstract(lambda: hybrid.init_params(jax.random.PRNGKey(0),
                                                 cfg))
    pool = on_chip((cfg.kv_layers, pages, page, cfg.kv_heads * cfg.d_head),
                   jnp.bfloat16)
    window = {}
    if cfg.window_layers:
        # the window layers' pool, sized as the server sizes it
        # (PagedKVCache: the cap from the window and one advance)
        chunk = payload["serving_prefill_chunk"]
        cap = -(-(cfg.attention_window
                  + max(chunk, payload["serving_window"])) // page) + 1
        wpool = on_chip((cfg.window_layers, slots * cap, page,
                         cfg.kv_heads * cfg.d_head), jnp.bfloat16)
        window = dict(win_pool_k=wpool, win_pool_v=wpool,
                      win_tables=on_chip((slots, cap), jnp.int32),
                      win_first=on_chip((slots,), jnp.int32))
    state = kvcache.PagedState(
        pool_k=pool, pool_v=pool,
        tables=on_chip((slots, cfg.max_seq // page), jnp.int32),
        lengths=on_chip((slots,), jnp.int32),
        recurrent=abstract(lambda: hybrid.fresh_recurrent(cfg, slots)),
        **window)
    if program == "prefill":
        lowered = kvcache._paged_prefill.lower(
            params, state,
            on_chip((payload.get("serving_prefill_chunk", 64),), jnp.int32),
            on_chip((), jnp.int32), cfg, on_chip((), jnp.int32))
    else:
        def row(dtype):
            return on_chip((slots,), dtype)

        lowered = kvcache._paged_decode_window_capped.lower(
            params, state, row(jnp.int32), cfg, payload["serving_window"],
            row(jnp.bool_), row(jnp.int32), row(jnp.int32))
    return cfg, params, state, lowered


@pytest.mark.parametrize("program", ["decode_window", "prefill"])
def test_the_patterned_cell_fits_and_leaves_its_state_where_it_is(
        chip, monkeypatch, program):
    """Compiled for the chip at the cell's shapes: the weights are 9.5
    GB in bf16 and no leaf is float32 but the small ones; the window and
    the prefill chunk fit the chip beside them with room (ISSUE 33's
    line for falling back to 48 slots is 15.0 GB); and the recurrent
    state rides the layer loop's carry as the pool does: no temporary of
    its size (2.4 GB) or of a layer's (0.27 GB: a prefill chunk once
    copied every row's state into another layout and back, 2.4 GB of
    temporaries). The decode window holds the one-pass SSM step kernel
    (ops/ssm_step.py) once for each of the period's nine mamba layers,
    handed the stacked state whole: no copy of it, and no more memory
    than the 12.81 GB the window needed with the state updated by XLA's
    own fusions (PERF.md section 4, PR 33)."""
    cfg, params, state, lowered = _patterned_cell_program(program, chip,
                                                          monkeypatch)
    leaves = jax.tree_util.tree_leaves(params)
    weights = sum(a.size * a.dtype.itemsize for a in leaves)
    assert 9.4e9 < weights < 9.6e9
    assert max(a.size for a in leaves if a.dtype == jnp.float32) \
        == cfg.n_layers * cfg.d_model * cfg.n_experts  # the router
    rows = state.recurrent["ssm"]
    assert rows.shape == (9, 64, 8192, 128) and rows.dtype == jnp.float32
    compiled = lowered.compile()
    text = compiled.as_text()
    # The window's step body: nine mamba layers and the attention layer.
    # A prefill chunk, one row's slot given, takes neither kernel.
    window = program == "decode_window"
    assert text.count('custom_call_target="tpu_custom_call"') \
        == (10 if window else 0)
    assert lowered.as_text().count('kernel_name = "ssm_step"') \
        == (9 if window else 0)
    if window:
        # Nothing but the kernel makes an array of the state's size: it
        # comes in as a parameter and goes through the nine calls.
        makers = set(re.findall(
            r" = f32\[9,64,8192,128\]\{[^}]*\} ([\w-]+)\(", text))
        assert makers <= {"custom-call", "parameter", "get-tuple-element"}, \
            makers
    memory = compiled.memory_analysis()
    needs = (memory.argument_size_in_bytes + memory.output_size_in_bytes
             - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    print(f"{program} at the patterned cell's shapes: needs "
          f"{needs / 1e9:.3f} GB, {memory.temp_size_in_bytes / 1e9:.3f} GB "
          f"of it temporaries")
    assert needs < 15.0e9
    if window:
        assert needs <= 12.815e9
    layer_state = rows.size * 4 // rows.shape[0]
    assert memory.temp_size_in_bytes < layer_state // 2, (
        f"{program}: {memory.temp_size_in_bytes / 1e9:.2f} GB of "
        "temporaries is a layer's recurrent state or more")
    # donated and updated in place: what comes out aliases what went in
    assert memory.alias_size_in_bytes >= rows.size * 4


@pytest.mark.parametrize("program", ["decode_window", "prefill"])
def test_the_delta_cell_fits_and_leaves_its_state_where_it_is(
        chip, monkeypatch, program):
    """``solar-open2-250b.batchgen`` compiled for the chip at its
    shapes, held to ISSUE 36's arithmetic: 3.308 B parameters, 6.62 GB
    in bf16, no leaf float32 but the small ones; 64 slots of three
    delta layers' state, [64 x 128, 128] float32 a layer and slot, the
    same array a mamba layer keeps; the 32-step window and the prefill
    chunk need under 9.5 GB with no temporary of a layer's state
    (0.27 GB) or of a weight's size, and the state donated and updated
    in place. The window holds four kernels: the one-pass delta step
    (ops/delta_step.py) once for each of the period's three delta
    layers, handed the stacked state whole, and the paged attention
    kernel at a query group of 8 (64 query heads), and beside them,
    once a layer, the walk over the touched experts (ops/expert_walk.py:
    320 experts, 8 a token and 64 rows leave a fifth of them untouched
    under even routing, so ``moe.walks_touched`` takes it), handed the
    experts' stacked leaves whole. A prefill chunk, one row's slot
    given, takes neither of the first two, and the walk by the same
    rule as the window: a chunk's 64 tokens are a batch's."""
    cfg, params, state, lowered = _patterned_cell_program(
        program, chip, monkeypatch, "solar-open2-250b.batchgen")
    leaves = jax.tree_util.tree_leaves(params)
    weights = sum(a.size * a.dtype.itemsize for a in leaves)
    assert sum(a.size for a in leaves) == pytest.approx(3.308e9, rel=1e-3)
    assert 6.6e9 < weights < 6.65e9
    assert max(a.size for a in leaves if a.dtype == jnp.float32) \
        == cfg.n_layers * cfg.d_model * cfg.n_experts  # the router
    assert (cfg.n_heads, cfg.kv_heads, cfg.d_head) == (64, 8, 128)
    rows = state.recurrent["ssm"]
    assert rows.shape == (3, 64, 64, 128, 128) and rows.dtype == jnp.float32
    assert state.recurrent["conv"].shape == (3, 64, 3 * 24576)
    assert state.pool_k.shape == (1, 1536, 128, 1024)
    compiled = lowered.compile()
    text = compiled.as_text()
    window = program == "decode_window"
    assert text.count('custom_call_target="tpu_custom_call"') \
        == (8 if window else 4)
    assert lowered.as_text().count('kernel_name = "delta_step"') \
        == (3 if window else 0)
    assert lowered.as_text().count('kernel_name = "expert_walk"') == 4
    if window:
        # Nothing but the kernel makes an array of the state's size: it
        # comes in as a parameter and goes through the three calls.
        makers = set(re.findall(
            r" = f32\[3,64,64,128,128\]\{[^}]*\} ([\w-]+)\(", text))
        assert makers <= {"custom-call", "parameter", "get-tuple-element"}, \
            makers
    # The experts' leaves come in as parameters and go to the walk as
    # they are: nothing makes an array of a layer's experts (1.26 GB).
    assert not re.findall(r" = bf16\[(?:1,)*40,4096,2560\]", text)
    memory = compiled.memory_analysis()
    needs = (memory.argument_size_in_bytes + memory.output_size_in_bytes
             - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    print(f"{program} at the delta cell's shapes: needs "
          f"{needs / 1e9:.3f} GB, {memory.temp_size_in_bytes / 1e9:.3f} GB "
          f"of it temporaries")
    assert needs < 9.5e9
    layer_state = rows.size * 4 // rows.shape[0]
    assert memory.temp_size_in_bytes < layer_state // 2, (
        f"{program}: {memory.temp_size_in_bytes / 1e9:.2f} GB of "
        "temporaries is a layer's recurrent state or more")
    assert memory.alias_size_in_bytes >= rows.size * 4


@pytest.mark.parametrize("program", ["decode_window", "prefill"])
def test_the_window_cell_fits_with_both_pools(chip, monkeypatch, program):
    """``smallthinker-21ba3b.longmix`` compiled for the chip at its
    shapes, held to ISSUE 40's arithmetic: 3.967 B parameters, 7.93 GB
    in bf16, no leaf float32 but the small ones; a pool of the two full
    layers' keys and values (2,816 pages of 0.5 MiB, 1.48 GB) and one of
    the six window layers' (2,240 = 64 x 35 pages of 1.5 MiB, 3.52 GB),
    both donated and updated in place; the 32-step window and the
    256-token prefill chunk need under the chip's 15.75 GB with no
    temporary of either pool's size. The window's step body, a scan
    over the two periods, holds four kernels: the paged attention
    kernel at a query group of 7 once for each layer of a period, one
    over a table of 64 pages and three, with the bound, over a table of
    a row's cap of 35. A prefill chunk, one row's slot given, takes the
    gather."""
    cfg, params, state, lowered = _patterned_cell_program(
        program, chip, monkeypatch, "smallthinker-21ba3b.longmix")
    leaves = jax.tree_util.tree_leaves(params)
    weights = sum(a.size * a.dtype.itemsize for a in leaves)
    assert sum(a.size for a in leaves) == pytest.approx(3.967e9, rel=1e-3)
    assert 7.92e9 < weights < 7.95e9
    assert max(a.size for a in leaves if a.dtype == jnp.float32) \
        == cfg.n_layers * cfg.d_model * cfg.n_experts  # the router
    assert (cfg.n_heads, cfg.kv_heads, cfg.d_head) == (28, 4, 128)
    assert (cfg.kv_layers, cfg.window_layers, cfg.ssm_layers) == (2, 6, 0)
    assert set(state.recurrent) == {"picks"}
    assert state.pool_k.shape == (2, 2816, 128, 512)
    assert state.win_pool_k.shape == (6, 2240, 128, 512)
    assert state.win_tables.shape == (64, 35)
    pools = 2 * (state.pool_k.size + state.win_pool_k.size) * 2
    assert pools == pytest.approx(1.476e9 + 3.523e9, rel=1e-3)
    compiled = lowered.compile()
    text = compiled.as_text()
    window = program == "decode_window"
    assert text.count('custom_call_target="tpu_custom_call"') \
        == (4 if window else 0)
    assert lowered.as_text().count('kernel_name = "paged_attention"') \
        == (4 if window else 0)
    memory = compiled.memory_analysis()
    needs = (memory.argument_size_in_bytes + memory.output_size_in_bytes
             - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    print(f"{program} at the window cell's shapes: needs "
          f"{needs / 1e9:.3f} GB, {memory.temp_size_in_bytes / 1e9:.3f} GB "
          f"of it temporaries")
    assert needs < 15.75e9
    assert memory.temp_size_in_bytes < state.pool_k.size * 2 // 2, (
        f"{program}: {memory.temp_size_in_bytes / 1e9:.2f} GB of "
        "temporaries is a layer of a pool or more")
    assert memory.alias_size_in_bytes >= pools


@pytest.mark.parametrize("program", ["decode_window", "prefill"])
def test_the_exaone_cell_fits_and_its_full_layer_takes_the_blocked_form(
        chip, monkeypatch, program):
    """``k-exaone-236b-a23b.longmix`` compiled for the chip at its
    shapes, held to ISSUE 43's arithmetic reckoned with the tree's own
    leaves: 3.715 B parameters, 7.43 GB in bf16, no leaf float32 but the
    small ones (the routers, their biases, the gains); a pool of the one
    full layer's keys and values (2,816 pages of 0.5 MiB, 1.48 GB) and
    one of the four window layers' (256 = 64 x 4 pages of 2 MiB, 0.54
    GB), both donated and updated in place. The window's program holds
    five kernels: the leading dense layer's, a window layer over a
    table of a row's cap of 4 pages, before the scan; in the period's
    body three more of those and, for the full layer, whose table of 64
    pages of a 1,024-wide pool at 64 heads does not fit the scratch
    whole, the blocked form, where the parent took the gather. A
    prefill chunk, one row's slot given, takes the gather."""
    cfg, params, state, lowered = _patterned_cell_program(
        program, chip, monkeypatch, "k-exaone-236b-a23b.longmix")
    leaves = jax.tree_util.tree_leaves(params)
    weights = sum(a.size * a.dtype.itemsize for a in leaves)
    assert sum(a.size for a in leaves) == pytest.approx(3.715e9, rel=1e-3)
    assert 7.42e9 < weights < 7.45e9
    assert max(a.size for a in leaves if a.dtype == jnp.float32) \
        == (cfg.n_layers - 1) * cfg.d_model * cfg.n_experts  # the routers
    assert (cfg.n_heads, cfg.kv_heads, cfg.d_head) == (64, 8, 128)
    assert (cfg.kv_layers, cfg.window_layers, cfg.ssm_layers) == (1, 4, 0)
    assert (cfg.dense_layers, cfg.leading_kinds, cfg.periods) \
        == (1, ("window",), 1)
    assert set(state.recurrent) == {"picks"}
    assert state.pool_k.shape == (1, 2816, 128, 1024)
    assert state.win_pool_k.shape == (4, 256, 128, 1024)
    assert state.win_tables.shape == (64, 4)
    pools = 2 * (state.pool_k.size + state.win_pool_k.size) * 2
    assert pools == pytest.approx(1.476e9 + 0.537e9, rel=1e-3)
    compiled = lowered.compile()
    text = compiled.as_text()
    window = program == "decode_window"
    assert text.count('custom_call_target="tpu_custom_call"') \
        == (5 if window else 0)
    lowered_text = lowered.as_text()
    assert lowered_text.count('kernel_name = "paged_attention"') \
        == (4 if window else 0)
    assert lowered_text.count('kernel_name = "paged_attention_blocked"') \
        == (1 if window else 0)
    memory = compiled.memory_analysis()
    needs = (memory.argument_size_in_bytes + memory.output_size_in_bytes
             - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    print(f"{program} at the exaone cell's shapes: needs "
          f"{needs / 1e9:.3f} GB, {memory.temp_size_in_bytes / 1e9:.3f} GB "
          f"of it temporaries")
    assert needs < 15.75e9
    assert memory.temp_size_in_bytes < state.pool_k.size * 2, (
        f"{program}: {memory.temp_size_in_bytes / 1e9:.2f} GB of "
        "temporaries is the full layer's pool or more")
    assert memory.alias_size_in_bytes >= pools


@pytest.mark.parametrize("program", ["pick_greedy", "pick_sampled",
                                     "join_firsts", "join_carry"])
def test_the_first_tokens_programs_compile_once_for_the_claimed_cell(
        chip, program):
    """What keeps a request's first token on the device
    (kvcache.PagedKVCache.pick_first and the two joins; ISSUE 47), at
    the shapes of ``granite-4.0-h-small.batchgen``: the chip's compiler
    takes each, the pick is one program a sampling mode (its arguments
    are the logits, the pool's row of first tokens and scalars: no
    bucket, no chunk length, and the slot is traced) and the join with
    the carry one a (window length, bucket)."""
    from benchmark import cellspec
    from kvedge_tpu.models import kvcache

    cell = cellspec.load_cell("granite-4.0-h-small.batchgen")
    payload = cell.config["payload"]
    slots, window = payload["serving_slots"], payload["serving_window"]
    vocab = cell.config["model"]["vocab"]

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    logits, firsts = on_chip((vocab,), jnp.float32), on_chip((slots,),
                                                             jnp.int32)
    slot, row = on_chip((), jnp.int32), on_chip((slots,), jnp.int32)
    if program == "pick_greedy":
        lowered = kvcache._pick_first_greedy.lower(logits, firsts, slot)
        shapes = [(vocab,), (slots,), ()]
    elif program == "pick_sampled":
        lowered = kvcache._pick_first_sampled.lower(
            logits, firsts, slot, on_chip((2,), jnp.uint32),
            on_chip((), jnp.float32), on_chip((), jnp.float32))
        shapes = [(vocab,), (slots,), (), (2,), (), ()]
    elif program == "join_firsts":
        lowered = kvcache._join_firsts.lower(row, firsts)
        shapes = [(slots,), (slots,)]
    else:
        lowered = kvcache._join_carry.lower(
            on_chip((window + 2, slots), jnp.int32), row, firsts,
            window - 1)
        shapes = [(window + 2, slots), (slots,), (slots,)]
    assert [a.shape for a in jax.tree_util.tree_leaves(
        lowered.in_avals)] == shapes
    compiled = lowered.compile()
    out = jax.tree_util.tree_leaves(compiled.out_avals if hasattr(
        compiled, "out_avals") else lowered.out_info)
    assert [tuple(a.shape) for a in out] == (
        [(slots,), ()] if program.startswith("pick") else [(slots,)])


def test_one_product_over_all_experts_does_not_fit_a_256_token_chunk(
        chip, monkeypatch):
    """Why ``moe.held_experts_ffn`` states the tokens once an expert
    above 64 of them: written as one product over all 64 experts, the
    window cell's 256-token prefill chunk has the chip's compiler copy
    the experts' stacked leaf transposed (3.75 GB), and the program
    needs 15.82 GB where the chip has 15.75. When this compiles, the
    fork and ``_ONE_PRODUCT_TOKENS`` can go."""
    from kvedge_tpu.models import moe

    monkeypatch.setattr(moe, "_ONE_PRODUCT_TOKENS", 1 << 30)
    # a chunk's trace is cached by its shapes, not by the constant
    jax.clear_caches()
    try:
        _, _, _, lowered = _patterned_cell_program(
            "prefill", chip, monkeypatch, "smallthinker-21ba3b.longmix")
        with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
            lowered.compile()
    finally:
        jax.clear_caches()


def test_scale_budget_case_sits_on_the_budget():
    """The int8 case above is only an edge while it equals the budget."""
    from kvedge_tpu.ops.paged_attention import scales_fit_vmem

    assert scales_fit_vmem(64 * 128, 4)
    assert not scales_fit_vmem(65 * 128, 4)


def test_cap8192_case_sits_on_the_auto_routes_kernel_side():
    """64 pages of 128 is a cap "auto" still routes to the kernel, and
    the case above named for the budget is the edge: at 16 heads and a
    width of 256 a page of cap costs 8 KB of score rows and 64 KB of V
    image, and the landing pads are two blocks of 8 pages, K and V, 2
    MB where they were four single pages: 12 MB hold 142 pages of cap
    (167 before the pads grew) and not 143."""
    from kvedge_tpu.ops.paged_attention import (
        block_pages, decode_scratch_fits_vmem,
    )

    assert block_pages(64, 128, 256) == 8
    assert decode_scratch_fits_vmem(64, 128, 256, 16)
    assert decode_scratch_fits_vmem(142, 128, 256, 16)
    assert not decode_scratch_fits_vmem(143, 128, 256, 16)
