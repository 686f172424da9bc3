"""The chips a run measures, and their peaks.

A run measures a TPU and nothing else: it fails where JAX finds no TPU,
fewer chips than the cell asks for, or a kind of chip that
``bench/peaks.json`` does not list.  It never falls back to the CPU.  Nor
does it report a cell's chips for a program that runs on fewer or others:
every program the cell serves has to take its inputs on exactly the cell's
chips.
"""
from __future__ import annotations

import json
import os

import jax

from .spec import BENCH_DIR


class NoChip(RuntimeError):
    """The run cannot measure what it was asked to."""


def load_peaks() -> dict:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        return json.load(f)


def chips(devices: list, count: int, peaks: dict) -> tuple[list, dict]:
    """The first ``count`` TPU devices and their kind's peaks."""
    if not devices or devices[0].platform != "tpu":
        platform = devices[0].platform if devices else "none"
        raise NoChip(f"no TPU: JAX sees {platform}")
    if len(devices) < count:
        raise NoChip(f"{count} chips asked for, JAX sees {len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise NoChip(f"no peaks for device kind {kind!r} in peaks.json")
    return devices[:count], peaks[kind]


def check_spans(name: str, program: jax.stages.Compiled,
                devices: list) -> None:
    """Raise ``NoChip`` unless the devices of ``program``'s input shardings
    are exactly ``devices``, the cell's chips."""
    on = set().union(*(s.device_set
                       for s in jax.tree.leaves(program.input_shardings)))
    if on != set(devices):
        raise NoChip(f"{name} runs on {len(on & set(devices))} of the "
                     f"cell's {len(devices)} chips"
                     + (f" and on {len(on - set(devices))} others"
                        if on - set(devices) else ""))


def describe(devices: list) -> dict:
    """The device as JAX reports it, with the peak memory of the fullest
    chip."""
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}
