"""The least time a chip could take for a count of operations and bytes.

The counts are a block's own (``references/<block>.py``'s
``decode_step``); this file knows no model. The peaks come from
``peaks.json``, keyed by ``device_kind``; a device that is not there is
an error, never a default.
"""

from __future__ import annotations

from benchmark import schedule


def peaks(device_kind: str) -> dict:
    table = schedule.load_json(".", "peaks")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmark/peaks.json")
    return table[device_kind]


def least_seconds(work: dict, peak: dict, chips: int = 1) -> float:
    """The roofline bound: the larger of compute time and memory time,
    on ``chips`` chips that split both evenly."""
    return max(work["flops"] / peak["bf16_flops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"]) / chips
