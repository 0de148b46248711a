"""Operations and bytes the algorithm needs, from shapes, and the least
time a chip could take for them.

These count what the model's equations require, not what the program
happens to read: weights once a decode step in the type they are
multiplied in (bf16), the live keys and values once. The peaks come from
``peaks.json``, keyed by ``device_kind``; a device that is not there is
an error, never a default.
"""

from __future__ import annotations

from benchmark import schedule

BF16 = 2


def peaks(device_kind: str) -> dict:
    table = schedule.load_json(".", "peaks")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmark/peaks.json")
    return table[device_kind]


def layer_params(model: dict) -> int:
    """Matrix parameters of one block: fused q|k|v, output projection,
    feed-forward up and down (no biases, the gains are not counted)."""
    d, h, kv, f = (model["d_model"], model["n_heads"], model["n_kv_heads"],
                   model["d_ff"])
    dh = d // h
    return d * (h + 2 * kv) * dh + h * dh * d + 2 * d * f


def matrix_params(model: dict) -> int:
    """All matrices a token passes through, the tied head included."""
    return (model["n_layers"] * layer_params(model)
            + model["vocab"] * model["d_model"])


def kv_bytes_per_token(model: dict) -> int:
    dh = model["d_model"] // model["n_heads"]
    return model["n_layers"] * 2 * model["n_kv_heads"] * dh * BF16


def decode_step(model: dict, rows: float, live_tokens: float) -> dict:
    """One decode step over ``rows`` sequences holding ``live_tokens``
    cached positions between them."""
    d, h = model["d_model"], model["n_heads"]
    dh = d // h
    flops = (2.0 * matrix_params(model) * rows
             + 4.0 * model["n_layers"] * h * dh * live_tokens)
    nbytes = (BF16 * matrix_params(model)
              + kv_bytes_per_token(model) * (live_tokens + rows))
    return {"flops": flops, "bytes": nbytes}


def least_seconds(work: dict, peak: dict, chips: int = 1) -> float:
    """The roofline bound: the larger of compute time and memory time,
    on ``chips`` chips that split both evenly."""
    return max(work["flops"] / peak["bf16_flops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"]) / chips
