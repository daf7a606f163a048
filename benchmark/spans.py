"""The program's own spans and records, read back from its run ledger
(``BIGDL_TPU_RUN_DIR`` / ``ledger.set_run_dir``), and what the serving
metrics reconstruct from them."""

from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Sequence


def read_ledger(run_dir: str) -> List[dict]:
    """Every record of every ``events-*.jsonl`` under ``run_dir``, by
    monotonic time."""
    out: List[dict] = []
    for path in glob.glob(os.path.join(run_dir, "events-*.jsonl")):
        with open(path, encoding="utf-8") as f:
            for line in f:
                try:
                    out.append(json.loads(line))
                except ValueError:
                    continue            # a torn last line
    return sorted(out, key=lambda r: r.get("mono", 0.0))


def spans_named(records: Sequence[dict], name: str) -> List[dict]:
    return [r for r in records
            if r.get("type") == "span" and r.get("name") == name]


def decode_contexts(records: Sequence[dict], t0: float, t1: float
                    ) -> List[List[int]]:
    """Cached tokens of every active row at every decode step whose chunk
    started in ``[t0, t1]`` (monotonic seconds), replayed from the
    ``serve.prefill`` spans (slot, prompt length), the ``serve.decode``
    spans (steps a chunk) and the ``serve.request`` records (tokens a
    request ended with)."""
    ended: Dict[int, int] = {r["rid"]: int(r.get("tokens", 0))
                             for r in records
                             if r.get("type") == "serve.request"
                             and r.get("status") == "ok"}
    pos: Dict[int, int] = {}
    limit: Dict[int, int] = {}
    steps_out: List[List[int]] = []
    for r in records:
        if r.get("type") != "span":
            continue
        a = r.get("attrs", {})
        if r["name"] == "serve.prefill":
            slot, tp = int(a["slot"]), int(a["tp"])
            n = ended.get(a.get("rid"))
            if n == 1:
                continue                 # resolved at its first token
            pos[slot] = tp
            limit[slot] = tp + n - 1 if n else 1 << 30
        elif r["name"] == "serve.decode":
            inside = t0 <= r["mono"] <= t1
            for _ in range(int(a.get("steps", 1))):
                if inside:
                    steps_out.append(list(pos.values()))
                for slot in list(pos):
                    pos[slot] += 1
                    if pos[slot] >= limit[slot]:
                        del pos[slot], limit[slot]
    return [s for s in steps_out if s]
