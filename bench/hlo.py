"""Counts read from a compiled step: its memory and its collectives.

``parse_collectives`` is a copy of the trainer's ``analysis/hlo.py`` parser,
kept here so that no later change to the program changes what the benchmark
counts.  Ring-model bytes per device that cross links:

    all-reduce        2 * bytes * (n-1)/n
    all-gather        result_bytes * (n-1)/n
    reduce-scatter    result_bytes * (n-1)
    all-to-all        bytes * (n-1)/n
    collective-permute bytes
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_OP_KINDS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "ragged-all-to-all",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_EXPLICIT_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_OP_RE = re.compile(r"=\s+(.+?)\s+([\w-]+)\(")


@dataclasses.dataclass
class CollectiveStats:
    kind: str
    count: int = 0
    raw_bytes: float = 0.0  # payload bytes per device program
    link_bytes: float = 0.0  # ring-model bytes crossing links per device


def _shape_bytes(text: str) -> float:
    total = 0.0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_EXPLICIT_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    return default


def parse_collectives(hlo_text: str, default_group: int = 1) -> Dict[str, CollectiveStats]:
    """Per-kind collective stats of one per-device HLO program."""
    stats = {k: CollectiveStats(kind=k) for k in _OP_KINDS}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line.strip())
        if not m:
            continue
        op = m.group(2)
        base = next((k for k in _OP_KINDS
                     if op == k or op.startswith(k + "-start")), None)
        if base is None:
            continue
        payload = _shape_bytes(m.group(1))
        n = max(_group_size(line, default_group), 1)
        st = stats[base]
        st.count += 1
        st.raw_bytes += payload
        if base == "all-reduce":
            st.link_bytes += 2.0 * payload * (n - 1) / n
        elif base == "all-gather":
            st.link_bytes += payload * (n - 1) / n
        elif base == "reduce-scatter":
            st.link_bytes += payload * (n - 1)
        elif base in ("all-to-all", "ragged-all-to-all"):
            st.link_bytes += payload * (n - 1) / n
        else:
            st.link_bytes += payload
    return {k: v for k, v in stats.items() if v.count}


def summarize(stats: Dict[str, CollectiveStats]) -> Dict:
    return {k: {"count": v.count, "raw_bytes": v.raw_bytes,
                "link_bytes": v.link_bytes} for k, v in stats.items()}


def link_bytes(hlo_text: str, group: int) -> float:
    return sum(s.link_bytes for s in parse_collectives(hlo_text, group).values())


def custom_call_count(hlo_text: str) -> int:
    """Pallas (Mosaic) kernels in the compiled program."""
    return hlo_text.count('custom_call_target="tpu_custom_call"')


def step_bytes(mem) -> int:
    """Per-device bytes of a compiled program: arguments + temporaries +
    outputs, less the outputs that alias a donated argument."""
    return (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
