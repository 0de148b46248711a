"""Demo step: resumable training -> checkpoint -> serving, one state volume.

Driven by tools/record_demo.py for the asciinema cast: actually runs the
``train`` payload (real feeder, real orbax checkpoints) and then the
``serve`` payload against the SAME state directory, proving the restored
step and a live generation — the round-2 half of the end-to-end story
(the resilience drill in demo_cluster.py is the round-1 half).

With ``--flagship`` the run sizes the payload through the ``[model]``
TOML section instead of the probe default: the 41.6M-param flagship —
the exact shape ``__graft_entry__`` reports numbers for — trains, checkpoints, and
serves through the same product path on the TPU. That scene needs the
chip: on any other backend the device check fails the payload instead
of quietly running it there (the committed cast's copy of the scene
says ``platform=cpu``: it was recorded when the scene took whatever
backend it found, and is due a re-recording on the chip).

Usage: python tools/demo_train_serve.py <corpus.kvfeed> [--flagship]
"""

from __future__ import annotations

import dataclasses
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    args = [a for a in sys.argv[1:] if a != "--flagship"]
    flagship = "--flagship" in sys.argv[1:]
    if len(args) != 1:
        print("Usage: python tools/demo_train_serve.py <corpus.kvfeed> "
              "[--flagship]")
        return 1
    corpus = args[0]
    # The cast is a COMMITTED artifact: library warnings (e.g. orbax's
    # restore-topology UserWarning, which embeds the recording machine's
    # site-packages path) would bake environment-specific noise into it
    # and churn the file on every regeneration.
    import warnings

    warnings.simplefilter("ignore")
    from kvedge_tpu.config.runtime_config import ModelSpec, RuntimeConfig
    from kvedge_tpu.runtime.workload import (
        run_serve_payload,
        run_train_payload,
        train_model_config,
    )

    state_dir = os.path.join(os.path.dirname(os.path.abspath(corpus)),
                             "state" + ("-flagship" if flagship else ""))
    platform = "tpu" if flagship else "cpu"
    base = dataclasses.replace(
        RuntimeConfig(),
        name="edge-tpu-demo",
        state_dir=state_dir,
        expected_platform=platform,
        status_port=0,
        status_bind="127.0.0.1",
        model=ModelSpec(preset="flagship" if flagship else ""),
        train_corpus=os.path.abspath(corpus),
        train_steps=4,
        train_batch=8,
        train_seq=16 if not flagship else 64,
        train_checkpoint_every=2,
    )

    if flagship:
        import jax

        device = jax.devices()[0]
        tcfg, _ = train_model_config(base)
        print(f"[model] preset = \"flagship\": {tcfg.param_count:,} params "
              f"(d_model={tcfg.d_model}, layers={tcfg.n_layers}, "
              f"vocab={tcfg.vocab}) on platform={device.platform} "
              f"device_kind={device.device_kind!r}")
    print("training 4 steps (checkpoint every 2) through the state volume...")
    result = run_train_payload(dataclasses.replace(base, payload="train"))
    if not result.ok:
        print(f"train payload failed: {result.error}")
        return 1
    print(f"train payload ok; final loss {result.probe_checksum:.3f}")

    print("booting the serve payload against the same state volume...")
    check, serve_fn = run_serve_payload(
        dataclasses.replace(base, payload="serve")
    )
    if not check.ok:
        print(f"serve payload failed: {check.error}")
        return 1
    out = serve_fn({"tokens": [[5, 9, 2, 7]], "n_new": 6})
    print(f"POST /generate -> restored_step={out['restored_step']} "
          f"tokens={out['tokens'][0]}")
    spec = serve_fn({"tokens": [[5, 9, 2, 7]], "n_new": 6,
                     "speculative": 4})
    print(f"POST /generate (speculative: 4) -> same tokens: "
          f"{spec['tokens'] == out['tokens']}, "
          f"accepted_per_step={spec['accepted_per_step']}")
    print("serving the trained checkpoint: restored_step matches the "
          "training target")

    if not flagship:
        # The continuous-batching backend, on the same checkpoint:
        # streamed tokens, device-side decode windows, chunked prefill,
        # and prefix sharing between requests with a common prompt.
        print("rebooting with [payload] serving = \"paged\" "
              "(continuous batching)...")
        check, paged_fn = run_serve_payload(dataclasses.replace(
            base, payload="serve", payload_serving="paged",
            serving_page_size=4, serving_prefill_chunk=4,
        ))
        if not check.ok:
            print(f"paged serve payload failed: {check.error}")
            return 1
        shared = [5, 9, 2, 7, 1, 3, 3, 8]  # two full 4-token KV pages
        first = paged_fn({"tokens": [shared + [4, 6]], "n_new": 4})
        print(f"POST /generate (paged) -> tokens={first['tokens'][0]}")
        streamed = paged_fn({"tokens": [shared + [2]], "n_new": 4,
                             "stream": True})
        docs = list(streamed["_stream"])
        toks = [d["token"] for d in docs if "token" in d]
        print(f"POST /generate (stream: true, shared prefix) -> "
              f"tokens arrive one ndjson doc each: {toks}")
        stats = paged_fn.stats()
        print(f"prefix cache: hits={stats['prefix_hits']} "
              f"tokens_saved={stats['prefix_tokens_saved']} "
              f"(the second request prefilled only its suffix)")
        paged_fn.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
