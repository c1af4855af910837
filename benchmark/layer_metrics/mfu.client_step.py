"""End-to-end model FLOP/s utilisation of the untraced window: the
configuration's frozen training FLOPs a sample x real samples a second a
chip, over the chip's bf16 peak (peaks.json). Not a roofline share: it
counts idle time and padding against the program."""

import json
import os

META = {"layer": "client step", "unit": "%", "moves": "samples_per_s_chip"}


def applies(cell: dict) -> bool:
    return True


def read(summary: dict):
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, os.pardir, "peaks.json")) as f:
        peaks = json.load(f)
    kind = summary["device_kind"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    per_chip = summary["real_samples"] / summary["window_s"] / summary["chips"]
    return (100.0 * summary["train_flops_per_sample"] * per_chip
            / peaks[kind]["bf16_flops_per_s"])
