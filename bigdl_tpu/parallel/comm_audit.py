"""HLO collective audit for the distributed training step.

The reference treats per-iteration communication as a first-class measured
quantity: the driver logs "get weights average" / "aggregate gradient
time" per node every iteration (``optim/DistriOptimizer.scala:115-119,
148-151``, ``optim/Metrics.scala:27-117``).  In the TPU-native design
those phases are collectives *inside* one fused XLA program, so the
equivalent evidence comes from the compiled HLO itself:

* the whole step is ONE ``HloModule`` containing both the model compute
  (convolution/dot) and the collectives — the structural property that
  lets the scheduler interleave communication with compute;
* every collective op, with its payload shape, replica group size and
  the jax op it lowered from (``metadata op_name``) → exact per-phase
  byte counts, replacing hand-derived traffic estimates;
* the backend's scheduling choice: async ``-start``/``-done`` pairs vs
  synchronous instructions;
* the wire dtype the backend actually kept.  (Measured finding, r4: the
  CPU backend PROMOTES bf16 collectives to f32 — ``to_apply=..._promoted``
  regions, no native bf16 reduction — while the TPU backend keeps the
  bf16 wire.  Auditing only the authored jaxpr would have missed this.)

``audit_hlo_text`` is a pure parser (unit-tested on compiled programs);
``audit_distri_step`` builds + AOT-compiles the real
``make_distri_train_step`` program — on the current devices or on a
deviceless TPU topology (``topology="v5e:2x4"``), so the REAL TPU
multi-chip program is auditable on a box with one chip.  Run
``bench_comm.py`` at the repo root to produce ``BENCH_comm_r*.json``.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "collective-broadcast",
                "ragged-all-to-all")

# one array component of an HLO shape: dtype[d0,d1,...]
_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([\d,]*)\]")
# one HLO instruction: %name = SHAPE opcode(...), attrs
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.-]+\s*=\s*(.*?)\s+([a-z][\w-]*)\(", re.M)
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')


def _components(shape_str: str) -> List[int]:
    """Byte size of every array component in an HLO shape string —
    handles plain shapes (``bf16[22280]{0:T(1024)(128)(2,1)S(1)}``) and
    async-op tuples (``(f32[2785]{...}, f32[22280]{...}, u32[]{...})``).
    Layout/tiling annotations contain no ``dtype[...]`` tokens, so the
    component regex is unambiguous."""
    out = []
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        out.append(n * _DTYPE_BYTES[dtype])
    return out


def _phase(op_name: str) -> str:
    """Map a collective's jax-level op_name to the partitioned
    algorithm's phase (the reference's metric taxonomy).  The named
    scopes ``get_weights`` / ``aggregate_gradient`` (emitted by
    ``AllReduceParameter``) take precedence — they survive whatever op
    the collective lowers to, including the r5 all-to-all
    aggregate-gradient carrier."""
    # scopes first — they win over whatever op the collective lowers to
    if "get_weights" in op_name:
        return "get_weights"                 # sendWeightPartition+getWeights
    if "aggregate_gradient" in op_name:
        return "aggregate_gradient"          # putGradients+aggregate
    # op-name fallbacks for programs built without the named scopes
    if "all_gather" in op_name:
        return "get_weights"
    if "psum_scatter" in op_name or "reduce_scatter" in op_name:
        return "aggregate_gradient"
    if "psum" in op_name or "pmean" in op_name:
        return "state_reduction"             # loss / BN running stats
    return "other"


def _wire_bytes(base_op: str, full_bytes: int, group: int) -> int:
    """Per-device ICI traffic (send side) of one collective over its FULL
    logical buffer, assuming the bandwidth-optimal ring algorithm — the
    standard cost model (scaling book; same accounting the reference's
    BlockManager fetch counts imply): all-gather / reduce-scatter move
    (g-1)/g of the full buffer through each device; all-reduce =
    reduce-scatter + all-gather = 2x; permute/all-to-all move the local
    buffer once."""
    if group <= 1:
        return 0
    if base_op == "all-reduce":
        return 2 * full_bytes * (group - 1) // group
    # all-to-all keeps its own 1/g chunk local, so it prices like the
    # ring AG/RS — which is why it can carry the aggregate-gradient
    # phase at authored cost
    if base_op in ("all-gather", "reduce-scatter", "all-to-all",
                   "ragged-all-to-all"):
        return full_bytes * (group - 1) // group
    return full_bytes


def audit_hlo_text(text: str) -> dict:
    """Parse optimized HLO → per-collective inventory with byte counts
    and phase attribution.  Returns::

        {"n_modules", "has_compute", "collectives": [{"op", "base_op",
         "async", "dtype", "buffer_bytes", "group_size", "phase",
         "op_name", "wire_bytes_per_device"}...],
         "phase_wire_bytes": {phase: total per-device wire bytes},
         "wire_dtypes": [...], "async_starts", "sync_collectives"}

    ``buffer_bytes``: the logical transfer buffer — result for sync ops;
    for async ``-start`` tuples the largest component (= result for
    all-gather, = operand for reduce-scatter, = the buffer for
    all-reduce), which is exactly the size the ring cost model needs.
    ``-done`` ops are skipped (their result aliases the start's buffer).
    """
    n_modules = len(re.findall(r"^HloModule\b", text, re.M))
    has_compute = bool(re.search(r"\b(convolution|dot)\b", text))
    collectives: List[dict] = []
    for m in _INSTR_RE.finditer(text):
        shape_str, opcode = m.group(1), m.group(2)
        base = opcode
        is_async = False
        for suffix in ("-start", "-done", "-update"):
            if base.endswith(suffix):
                base = base[: -len(suffix)]
                is_async = True
        if base not in _COLLECTIVES or opcode.endswith(("-done", "-update")):
            continue
        comps = _components(shape_str)
        if base in ("all-to-all", "ragged-all-to-all"):
            # backends may lower a2a in tuple form (one component per
            # peer chunk — the CPU backend does); the full local buffer
            # is the SUM of the chunks.  Async -start tuples carry
            # operands AND results (equal halves) — halve the sum.
            # Skip the 4-byte u32 async-context scalars.
            arrs = [b for (dt, dims), b in
                    zip(_SHAPE_RE.findall(shape_str), comps)
                    if not (dt in ("u32", "s32") and not dims)]
            total = sum(arrs)
            buffer_bytes = total // 2 if is_async else total
        else:
            buffer_bytes = max(comps) if comps else 0
        line = text[m.start():text.find("\n", m.start())]
        gm = _GROUPS_RE.search(line)
        if gm:
            group = len(gm.group(1).split(","))
        else:
            gi = _GROUPS_IOTA_RE.search(line)
            group = int(gi.group(2)) if gi else 1
        onm = _OPNAME_RE.search(line)
        op_name = onm.group(1) if onm else ""
        dm = _SHAPE_RE.search(shape_str)
        # the FULL logical buffer the ring model prices: a sync
        # reduce-scatter's result is the per-device shard, so the full
        # reduced buffer is result * group; every other form (sync
        # all-gather result, async -start operand via max component,
        # all-reduce buffer) is already the full size
        full = buffer_bytes * group \
            if (base == "reduce-scatter" and not is_async) else buffer_bytes
        collectives.append({
            "op": opcode, "base_op": base, "async": is_async,
            "dtype": dm.group(1) if dm else "?",
            "buffer_bytes": full, "group_size": group,
            "phase": _phase(op_name) if op_name else "unattributed",
            "op_name": op_name,
            "wire_bytes_per_device": _wire_bytes(base, full, group)})
    phase_wire: Dict[str, int] = {}
    for c in collectives:
        phase_wire[c["phase"]] = (phase_wire.get(c["phase"], 0) +
                                  c["wire_bytes_per_device"])
    return {
        "n_modules": n_modules,
        "has_compute": has_compute,
        "collectives": collectives,
        "phase_wire_bytes": phase_wire,
        "wire_dtypes": sorted({c["dtype"] for c in collectives}),
        "async_starts": sum(1 for c in collectives if c["async"]),
        "sync_collectives": sum(1 for c in collectives if not c["async"]),
    }


def schedule_overlap(text: str) -> List[dict]:
    """For every async collective ``-start`` in the (schedule-ordered)
    compiled module, how much work the scheduler actually placed between
    it and its ``-done`` — the difference between an async op that
    merely exists and one that HIDES latency.  Counts scheduled
    instructions in between and how many of them are compute
    (fusion/convolution/dot).  A compiled TPU module's text is emitted
    in schedule order, so textual distance inside one computation is
    schedule distance."""
    out = []
    starts: Dict[str, dict] = {}
    pos = 0
    instr_re = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=\s*\S.*?"
                          r"\s([a-z][\w-]*)\(", )
    compute_re = re.compile(r"\b(fusion|convolution|dot)\b")
    # the -done's operand is the matching -start; tolerate a typed
    # operand form ("dtype[dims] %name") as well as the bare "%name"
    # this toolchain prints
    done_operand_re = re.compile(
        r"\(\s*(?:[a-z]\w*\[[\d,]*\][^\s%]*\s+)?%?([\w.-]+)")
    for line in text.splitlines():
        m = instr_re.match(line)
        if not m:
            continue
        pos += 1
        name, opcode = m.group(1), m.group(2)
        if opcode.endswith("-start") and \
                opcode[:-6].rstrip("-") in _COLLECTIVES:
            starts[name] = {"op": opcode, "pos": pos, "compute": 0}
        else:
            is_compute = bool(compute_re.search(opcode))
            if is_compute:
                for rec in starts.values():
                    rec["compute"] += 1
        if opcode.endswith("-done"):
            om = done_operand_re.search(line[m.end(2):])
            key = om.group(1) if om else None
            if key in starts:
                rec = starts.pop(key)
                out.append({
                    "op": rec["op"],
                    "instructions_between": pos - rec["pos"] - 1,
                    "compute_between": rec["compute"]})
    # a leftover start means the pair-matching failed to find its -done
    # — surface it as a parse miss instead of silently reading as "no
    # async overlap"
    for name, rec in starts.items():
        out.append({"op": rec["op"], "unmatched_start": name,
                    "instructions_between": None,
                    "compute_between": None})
    return out


def expected_step_traffic(layout, n: Optional[int] = None) -> dict:
    """Analytic per-iteration traffic of the partitioned algorithm — the
    numbers the HLO inventory is cross-checked against.

    getWeights: every device assembles the full padded flat vector from
    the n shards (all-gather); aggregateGradient: the full local gradient
    is reduce-scattered down to the owned shard.  Both phases move one
    padded-vector buffer in the wire dtype; per-device ring traffic is
    (n-1)/n of it (2x if the backend lowers the pair as all-reduces).
    """
    n = n or layout.n
    wire_itemsize = 2 if layout.compress == "bf16" else \
        layout.dtype.itemsize
    payload = int(layout.padded) * wire_itemsize
    axis = getattr(layout, "axis", "data")
    return {
        "n_devices": n,
        "ring_axes": list(axis) if isinstance(axis, tuple) else [axis],
        "param_count": int(layout.size),
        "padded_param_count": int(layout.padded),
        "wire_dtype": "bf16" if layout.compress == "bf16" else
        str(layout.dtype),
        "get_weights_buffer_bytes": payload,
        "aggregate_gradient_buffer_bytes": payload,
        "ring_wire_bytes_per_device_per_phase": payload * (n - 1) // n,
    }


def cross_check(audit: dict, expected: dict) -> dict:
    """Verify the compiled inventory carries the authored traffic
    contract.  The authored program (our own construction) moves exactly
    TWO parameter-payload buffers per step — getWeights (all-gather) and
    aggregateGradient (reduce-scatter), each ``padded_param_count`` in
    the wire dtype — plus small state reductions.  Backends may rewrite
    the op (TPU lowers both as all-reduce + slice at small sizes, losing
    metadata) or promote the wire dtype (CPU has no native bf16
    reductions: ``*_promoted`` regions, f32 wire) — the check accepts a
    payload match in either the wire dtype or the promoted master dtype
    and reports which via ``wire_dtype_kept``.  Returns dicts of
    booleans kept as data so the artifact shows WHAT was checked."""
    wire_payload = expected["get_weights_buffer_bytes"]
    promoted_payload = expected["padded_param_count"] * 4
    param_cols = [c for c in audit["collectives"]
                  if c["buffer_bytes"] in (wire_payload, promoted_payload)]
    # wire economy: the authored ZeRO-1 pattern pays (n-1)/n of the
    # payload per phase (AG + RS rings).  r1-r4 shipped a program whose
    # TPU lowering paid 2x that (both phases decomposed to full
    # all-reduces); r5's LANE-aligned all-gather + all-to-all carrier
    # recovers the authored bytes — this verdict fails the audit if a
    # toolchain bump ever silently re-doubles it.
    phase_wire = audit["phase_wire_bytes"]
    # decomposition passes (reduce-scatter-decomposer et al.) strip the
    # jax op_name metadata — a parameter-payload collective with no
    # attribution is still parameter traffic and MUST count against the
    # economy, else the exact failure this check exists for (silent
    # re-doubling via decomposition) would dodge it
    unattributed_param = sum(
        c["wire_bytes_per_device"] for c in audit["collectives"]
        if c["phase"] == "unattributed"
        and c["buffer_bytes"] in (wire_payload, promoted_payload))
    param_total = (phase_wire.get("get_weights", 0) +
                   phase_wire.get("aggregate_gradient", 0) +
                   unattributed_param)
    authored = 2 * wire_payload * (expected["n_devices"] - 1) \
        // expected["n_devices"]
    promoted_authored = 2 * promoted_payload * \
        (expected["n_devices"] - 1) // expected["n_devices"]
    # a promoted (f32) wire is judged against the promoted authored
    # bytes — dtype promotion is the separate wire_dtype_kept verdict,
    # not a wire-economy failure.  The denominator is picked from the
    # dtype the param collectives ACTUALLY carry (not min()'d — with
    # promoted = 2x authored exactly, a min() would score the 2x bf16
    # re-decomposition as 1.0 and defeat the check).
    promoted = any(c["dtype"] != expected["wire_dtype"]
                   for c in param_cols)
    denom = promoted_authored if promoted else authored
    ratio = param_total / denom if denom else float("inf")
    economy = {
        "param_phase_wire_bytes": param_total,
        "authored_ring_wire_bytes": authored,
        "wire_economy_ratio": round(ratio, 3),
        "wire_economy_ok": ratio <= 1.1,
    }
    return {
        **economy,
        "single_module": audit["n_modules"] == 1,
        "compute_and_comm_in_one_program": audit["has_compute"]
        and bool(audit["collectives"]),
        "parameter_payload_collectives": len(param_cols),
        "both_param_phases_present": len(param_cols) >= 2,
        "wire_dtype_kept": bool(param_cols) and all(
            c["dtype"] == expected["wire_dtype"] for c in param_cols),
        "groups_span_data_axis": all(
            c["group_size"] == expected["n_devices"]
            for c in audit["collectives"]) and bool(audit["collectives"]),
    }


def abstract_step_args(layout, optim, model_state, mesh,
                       batch_shape, dtype=None):
    """ShapeDtypeStructs for ``make_distri_train_step``'s step fn, laid
    out on ``mesh`` — AOT lowering needs no real buffers, which is what
    lets a deviceless TPU topology compile the multi-chip program."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    def sds(shape, dt, spec):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, spec))

    n, ss = layout.n, layout.shard_size
    # the ring may be one axis ("data") or the data x fsdp tuple — P()
    # takes either form for the leading dim
    axis = layout.axis
    dtype = dtype or layout.dtype
    wshard = sds((n, ss), dtype, P(axis))
    opt_state = optim.init_state(jnp.zeros((ss,), dtype))
    opt_shard = jax.tree_util.tree_map(
        lambda t: sds((n,) + np.shape(t), np.asarray(t).dtype,
                      P(*((axis,) + (None,) * np.ndim(t)))), opt_state)
    state_a = jax.tree_util.tree_map(
        lambda t: sds(np.shape(t), np.asarray(t).dtype, P()), model_state)
    data = sds(batch_shape, jnp.float32, P(axis))
    labels = sds((batch_shape[0],), jnp.float32, P(axis))
    rng = sds((2,), jnp.uint32, P())
    stepno = sds((), jnp.int32, P())
    clr = sds((), jnp.float32, P())
    return wshard, opt_shard, state_a, data, labels, rng, stepno, clr


def audit_distri_step(model, criterion, optim, mesh, config, batch_shape,
                      compress: Optional[str] = "bf16",
                      rs_mode: str = "a2a",
                      compiler_options: Optional[dict] = None,
                      compute_dtype=None) -> dict:
    """AOT-compile the full distributed train step on ``mesh`` (real
    devices or a deviceless topology) and audit its HLO.  Returns the
    ``audit_hlo_text`` result plus the analytic ``expected`` traffic and
    the ``cross_check`` verdicts.  ``compiler_options`` are forwarded to
    the XLA compile (e.g. the latency-hiding-scheduler experiment);
    ``compute_dtype`` is the step's own (pass what the trainer runs to
    audit the trainer's exact program)."""
    from bigdl_tpu.parallel.allreduce import make_distri_train_step

    step, layout, _ = make_distri_train_step(
        model, criterion, optim, mesh, config, compress=compress,
        params_template=model.params, rs_mode=rs_mode,
        compute_dtype=compute_dtype)
    args = abstract_step_args(layout, optim, model.state, mesh,
                              batch_shape)
    lowered = step.lower(*args)
    compiled = lowered.compile(compiler_options=compiler_options) \
        if compiler_options else lowered.compile()
    text = compiled.as_text()
    audit = audit_hlo_text(text)
    audit["expected"] = expected_step_traffic(layout)
    audit["checks"] = cross_check(audit, audit["expected"])
    audit["schedule_overlap"] = schedule_overlap(text)
    audit["rs_mode"] = rs_mode
    if compiler_options:
        audit["compiler_options"] = dict(compiler_options)
    audit["hlo_chars"] = len(text)
    return audit
