"""XLA cost attribution and roofline analysis.

PR 4's telemetry stack measures *wall-clock* (timeline spans, step times,
HBM highwater) but attributes nothing against the hardware's peak — so
"0.48 MFU" cannot answer *why not 0.6*: is the step compute-,
memory-, or comms-bound?  This module closes that gap with the accounting
discipline Megatron-LM uses to make MFU claims defensible (Narayanan et
al., arXiv:2104.04473):

* **Cost extraction** — XLA's own ``cost_analysis()`` (flops, bytes
  accessed, transcendentals) and ``memory_analysis()`` pulled from the
  jitted executables the run *actually dispatches* (train step, serving
  prefill/decode buckets).  Two tiers, chosen by call site:

  - :func:`lower_cost_profile` only *lowers* (no XLA compile) — cheap
    enough for the end of every fit, gives program-level totals;
  - :func:`aot_profile` lowers **and** compiles — the ``llmtrain
    profile`` CLI's path, which additionally yields post-optimization
    HLO for the per-op table, compile wall-times, and the compiled
    memory footprint.

* **Roofline attribution** — against the per-device-kind table
  (``utils/hw.py:DEVICE_TABLE``, config-overridable), each executable and
  each top-k HLO op category is classified compute-/memory-/comms-bound by
  comparing ``flops/peak_flops`` vs ``bytes/hbm_bw`` vs
  ``collective_bytes/ici_bw`` (Williams et al. roofline model).

* **MFU reconciliation** — the analytical MFU (XLA-counted flops) is
  compared against the measured tokens/s MFU (PaLM ``6N`` approximation,
  utils/hw.py).  Their ratio is *deterministic* (step time cancels):
  ``xla_flops_per_step / (tokens_per_step * palm_flops_per_token)`` — a
  value far outside [0.5, 2.0] means one of the two flop models is wrong
  for this architecture, and the report says so.

Everything here is pure measurement: no function in this module executes
device code, mutates donated buffers, or raises into a step loop (cost
hooks degrade to ``None``/empty on any backend oddity).
"""

from __future__ import annotations

import re
import time
from typing import Any, Iterable, Mapping

from ..utils.hw import device_row
from ..utils.logging import get_logger

logger = get_logger()

# --------------------------------------------------------------------------
# Device peaks
# --------------------------------------------------------------------------

_PEAK_KEYS = ("peak_flops", "hbm_bytes_per_sec", "ici_bytes_per_sec")


def resolve_peaks(
    device_kind: str | None = None,
    overrides: Mapping[str, float] | None = None,
) -> dict[str, float]:
    """Roofline peaks of ``device_kind`` (None: the first local jax
    device) from the one device table (``utils/hw.py:device_row``, which
    raises for an unknown TPU kind), with the ``telemetry.device_peaks``
    config overrides merged on top."""
    row = device_row(device_kind)  # None: the lookup reads platform and kind
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    peaks = {key: float(row[key]) for key in _PEAK_KEYS}
    peaks["device_kind"] = device_kind.lower()  # type: ignore[assignment]
    for key in _PEAK_KEYS:
        if overrides and key in overrides and overrides[key]:
            peaks[key] = float(overrides[key])
    return peaks


# --------------------------------------------------------------------------
# cost_analysis normalization
# --------------------------------------------------------------------------


def normalize_cost(raw: Any) -> dict[str, float]:
    """Flatten XLA ``cost_analysis()`` output to ``{property: float}``.

    The API shape differs by object: ``Lowered.cost_analysis()`` returns a
    plain dict, ``Compiled.cost_analysis()`` a list of per-computation
    dicts (first entry = entry computation), and either may be ``None`` on
    exotic backends.  All shapes land in one flat dict here.
    """
    if raw is None:
        return {}
    if isinstance(raw, (list, tuple)):
        raw = raw[0] if raw else {}
    out: dict[str, float] = {}
    try:
        for key, value in dict(raw).items():
            if isinstance(value, (int, float)):
                out[str(key)] = float(value)
    except Exception:  # noqa: BLE001
        return {}
    return out


def cost_summary(raw: Any) -> dict[str, float]:
    """The three headline properties from a raw ``cost_analysis()``."""
    cost = normalize_cost(raw)
    return {
        "flops": cost.get("flops", 0.0),
        "bytes_accessed": cost.get("bytes accessed", cost.get("bytes_accessed", 0.0)),
        "transcendentals": cost.get("transcendentals", 0.0),
    }


def memory_summary(compiled: Any) -> dict[str, float]:
    """``Compiled.memory_analysis()`` as a JSON-friendly dict (or {})."""
    try:
        mem = compiled.memory_analysis()
    except Exception:  # noqa: BLE001
        return {}
    if mem is None:
        return {}
    out: dict[str, float] = {}
    for attr in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        value = getattr(mem, attr, None)
        if isinstance(value, (int, float)):
            out[attr] = float(value)
    if out:
        out["total_hbm_bytes"] = (
            out.get("argument_size_in_bytes", 0.0)
            + out.get("output_size_in_bytes", 0.0)
            + out.get("temp_size_in_bytes", 0.0)
        )
    return out


# --------------------------------------------------------------------------
# Post-optimization HLO parsing (per-op cost table)
# --------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\](?:\{[^}]*\})?")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?P<rtype>\([^=]*?\)|\S+)\s+"
    r"(?P<opcode>[a-z][\w\-]*)\((?P<rest>.*)$"
)

# Opcodes whose cost is pure data movement (bytes counted, flops 0).
_ZERO_FLOP_OPS = frozenset({
    "parameter", "constant", "iota", "tuple", "get-tuple-element",
    "bitcast", "bitcast-convert", "copy", "copy-start", "copy-done",
    "reshape", "transpose", "broadcast", "slice", "dynamic-slice",
    "dynamic-update-slice", "concatenate", "pad", "reverse", "gather",
    "scatter", "after-all", "custom-call", "fusion", "call",
    "rng-bit-generator", "rng", "while", "conditional", "convolution",
    "optimization-barrier", "domain", "partition-id", "replica-id",
    "infeed", "outfeed", "send", "recv", "send-done", "recv-done",
})

_TRANSCENDENTAL_OPS = frozenset({
    "exponential", "exponential-minus-one", "log", "log-plus-one",
    "logistic", "tanh", "sqrt", "rsqrt", "cbrt", "power", "sine",
    "cosine", "tan", "atan2", "erf",
})

_COLLECTIVE_OPS = frozenset({
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "all-reduce-start",
    "all-gather-start", "collective-permute-start",
})

_REDUCE_OPS = frozenset({"reduce", "reduce-window", "sort", "select-and-scatter"})

_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")


def _shapes_bytes(text: str) -> tuple[float, float]:
    """(total bytes, total elements) over every shape literal in ``text``."""
    total_bytes = 0.0
    total_elems = 0.0
    for dtype, dims in _SHAPE_RE.findall(text):
        size = _DTYPE_BYTES.get(dtype)
        if size is None:
            continue
        elems = 1.0
        for d in dims.split(","):
            if d:
                elems *= float(d)
        total_elems += elems
        total_bytes += elems * size
    return total_bytes, total_elems


def _split_operands(rest: str) -> tuple[str, str]:
    """Split ``rest`` (text after the opening paren of ``opcode(``) into
    (operand text, attribute text) at the balanced closing paren."""
    depth = 1
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
            if depth == 0:
                return rest[:i], rest[i + 1 :]
    return rest, ""


def _dot_flops(rest: str, out_elems: float) -> float:
    """``2 * prod(output dims) * prod(contracting dim sizes)`` — the
    contracting sizes come from the first (lhs) operand shape plus the
    ``lhs_contracting_dims={...}`` attribute XLA prints inline."""
    operands, attrs = _split_operands(rest)
    match = _CONTRACT_RE.search(attrs) or _CONTRACT_RE.search(rest)
    lhs = _SHAPE_RE.search(operands)
    if match is None or lhs is None:
        return 2.0 * out_elems  # degraded guess: at least count the outputs
    dims = [d for d in lhs.group(2).split(",") if d]
    contract = 1.0
    for idx_text in match.group(1).split(","):
        if not idx_text:
            continue
        idx = int(idx_text)
        if 0 <= idx < len(dims):
            contract *= float(dims[idx])
    return 2.0 * out_elems * contract


def parse_hlo_ops(hlo_text: str) -> dict[str, Any]:
    """Aggregate per-opcode costs out of post-optimization HLO text.

    Accounting stance (documented in docs/observability.md):

    * **flops** are summed over *every* computation — an op fused into a
      loop fusion still does its math;
    * **bytes** are summed over the ENTRY computation only — only
      materialized buffers move through HBM, and fusion instructions at
      entry level carry exactly their operand+output traffic.

    Returns ``{"ops": {opcode: {...}}, "totals": {...},
    "collective_bytes": float}`` — all plain floats, JSON-ready.
    """
    ops: dict[str, dict[str, float]] = {}
    totals = {"flops": 0.0, "bytes_accessed": 0.0, "transcendentals": 0.0}
    collective_bytes = 0.0
    in_entry = False
    depth = 0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.startswith("ENTRY "):
            in_entry = True
            depth = stripped.count("{") - stripped.count("}")
            continue
        if in_entry:
            depth += stripped.count("{") - stripped.count("}")
            # Attribute braces ({1,0}, dims={...}) are balanced within a
            # line; only the computation's closing brace drops depth <= 0.
            if stripped == "}" or depth < 0:
                in_entry = False
        match = _INSTR_RE.match(line)
        if match is None:
            continue
        opcode = match.group("opcode")
        rtype = match.group("rtype")
        rest = match.group("rest")
        out_bytes, out_elems = _shapes_bytes(rtype)

        flops = 0.0
        transcendentals = 0.0
        if opcode == "dot":
            flops = _dot_flops(rest, out_elems)
        elif opcode in _REDUCE_OPS:
            operands, _ = _split_operands(rest)
            _, in_elems = _shapes_bytes(operands)
            flops = max(in_elems, out_elems)
        elif opcode in _COLLECTIVE_OPS:
            flops = out_elems if "reduce" in opcode else 0.0
        elif opcode in _ZERO_FLOP_OPS:
            flops = 0.0
        else:
            # Elementwise/default: one flop per output element.
            flops = out_elems
            if opcode in _TRANSCENDENTAL_OPS:
                transcendentals = out_elems

        entry = ops.setdefault(
            opcode,
            {"count": 0.0, "flops": 0.0, "bytes_accessed": 0.0, "transcendentals": 0.0},
        )
        entry["count"] += 1
        entry["flops"] += flops
        entry["transcendentals"] += transcendentals
        totals["flops"] += flops
        totals["transcendentals"] += transcendentals
        if in_entry and opcode != "parameter":
            operands, _ = _split_operands(rest)
            op_bytes, _ = _shapes_bytes(operands)
            entry["bytes_accessed"] += out_bytes + op_bytes
            totals["bytes_accessed"] += out_bytes + op_bytes
            if opcode in _COLLECTIVE_OPS:
                collective_bytes += op_bytes
    return {"ops": ops, "totals": totals, "collective_bytes": collective_bytes}


def top_ops(
    parsed: Mapping[str, Any],
    peaks: Mapping[str, float],
    *,
    k: int = 10,
) -> list[dict[str, Any]]:
    """The top-``k`` opcodes ranked by ``max(flops share, bytes share)``,
    each carrying its own roofline class — the human-readable "where does
    the cost go" table."""
    ops: Mapping[str, Mapping[str, float]] = parsed.get("ops", {})
    totals: Mapping[str, float] = parsed.get("totals", {})
    total_flops = max(totals.get("flops", 0.0), 1.0)
    total_bytes = max(totals.get("bytes_accessed", 0.0), 1.0)
    rows: list[dict[str, Any]] = []
    for opcode, entry in ops.items():
        flops_frac = entry["flops"] / total_flops
        bytes_frac = entry["bytes_accessed"] / total_bytes
        if opcode in _COLLECTIVE_OPS:
            op_class = "comms"
        elif entry["flops"] <= 0 and entry["bytes_accessed"] <= 0:
            continue  # parameters/tuples: no cost, no row
        else:
            compute_t = entry["flops"] / max(peaks.get("peak_flops", 1.0), 1.0)
            memory_t = entry["bytes_accessed"] / max(
                peaks.get("hbm_bytes_per_sec", 1.0), 1.0
            )
            op_class = "compute" if compute_t >= memory_t else "memory"
        rows.append(
            {
                "op": opcode,
                "count": int(entry["count"]),
                "flops": entry["flops"],
                "bytes_accessed": entry["bytes_accessed"],
                "flops_frac": round(flops_frac, 4),
                "bytes_frac": round(bytes_frac, 4),
                "class": op_class,
            }
        )
    rows.sort(key=lambda r: max(r["flops_frac"], r["bytes_frac"]), reverse=True)
    return rows[:k]


# --------------------------------------------------------------------------
# Roofline classification
# --------------------------------------------------------------------------


def classify_roofline(
    *,
    flops: float,
    bytes_accessed: float,
    peaks: Mapping[str, float],
    collective_bytes: float = 0.0,
) -> dict[str, Any]:
    """Classify one executable compute-/memory-/comms-bound.

    The class is the argmax of the three analytical times (flops/peak,
    bytes/hbm_bw, collective_bytes/ici_bw); ``arithmetic_intensity`` vs
    ``ridge_intensity`` (peak_flops/hbm_bw) restates the compute-vs-memory
    half on the classic roofline axes.
    """
    peak_flops = max(float(peaks.get("peak_flops", 1.0)), 1.0)
    hbm_bw = max(float(peaks.get("hbm_bytes_per_sec", 1.0)), 1.0)
    ici_bw = max(float(peaks.get("ici_bytes_per_sec", 1.0)), 1.0)
    compute_ms = flops / peak_flops * 1e3
    memory_ms = bytes_accessed / hbm_bw * 1e3
    comms_ms = collective_bytes / ici_bw * 1e3
    times = {"compute": compute_ms, "memory": memory_ms, "comms": comms_ms}
    bound = max(times, key=lambda key: times[key])
    return {
        "class": bound,
        "analytical_ms": {key: round(val, 6) for key, val in times.items()},
        "arithmetic_intensity": round(flops / max(bytes_accessed, 1.0), 4),
        "ridge_intensity": round(peak_flops / hbm_bw, 4),
    }


def gradient_collective_bytes(
    axis_sizes: Mapping[str, int], trainable_grad_bytes: float
) -> float:
    """Per-chip gradient-sync bytes per step: ring all-reduce moves
    ``2*(dp-1)/dp * grad_bytes`` over the combined data-parallel degree
    (the ``data``/``fsdp``/``expert`` axes — parallel/sharding.py
    ZERO_PARTITION_AXES).  0 when unsharded: no cross-chip sync."""
    dp = 1
    for axis in ("data", "fsdp", "expert"):
        dp *= max(int(axis_sizes.get(axis, 1)), 1)
    if dp <= 1:
        return 0.0
    return 2.0 * (dp - 1) / dp * float(trainable_grad_bytes)


# --------------------------------------------------------------------------
# Executable profiles (two tiers)
# --------------------------------------------------------------------------


def lower_cost_profile(
    jitted: Any, args: tuple, *, name: str, n_chips: int = 1
) -> dict[str, Any] | None:
    """Tier-1-budget-safe cost probe: trace+lower only, NO XLA compile.

    Returns cost totals — enough for roofline class and MFU
    reconciliation at the end of every fit.  ``Lowered.cost_analysis()``
    describes the GLOBAL (pre-SPMD-partitioning) program, unlike the
    per-shard ``Compiled`` figures :func:`aot_profile` mines, so callers
    running under a mesh pass ``n_chips`` and the totals normalize to the
    per-device frame both tiers report in.  Args may be live arrays or
    ShapeDtypeStructs; nothing executes, so donation annotations on
    ``jitted`` never consume a buffer.  Returns None on any failure
    (attribution is optional, the run is not).
    """
    try:
        t0 = time.perf_counter()
        lowered = jitted.lower(*args)
        lower_s = time.perf_counter() - t0
        summary = cost_summary(lowered.cost_analysis())
        for key in ("flops", "bytes_accessed", "transcendentals"):
            summary[key] /= max(int(n_chips), 1)
        summary["name"] = name
        summary["lower_time_s"] = round(lower_s, 4)
        return summary
    except Exception as exc:  # noqa: BLE001
        logger.debug("cost lowering for %s failed: %s", name, exc)
        return None


def aot_profile(
    jitted: Any,
    args: tuple,
    *,
    name: str,
    peaks: Mapping[str, float],
    collective_bytes: float = 0.0,
    top_k: int = 10,
    n_chips: int = 1,
) -> dict[str, Any] | None:
    """Full ahead-of-time profile: lower, compile, and mine the compiled
    executable — per-op table from post-optimization HLO, memory
    analysis, timed compile.  The ``llmtrain profile`` path; too slow for
    in-run hooks.  Never executes the program."""
    try:
        t0 = time.perf_counter()
        lowered = jitted.lower(*args)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
    except Exception as exc:  # noqa: BLE001
        logger.warning("AOT profile of %s failed at lower/compile: %s", name, exc)
        return None
    summary = cost_summary(compiled.cost_analysis())
    if summary["flops"] <= 0.0:  # some backends only report on the Lowered
        # The Lowered figures are global-program; normalize to the
        # per-device frame the Compiled figures are in.
        lowered_summary = cost_summary(lowered.cost_analysis())
        if lowered_summary["flops"] > 0.0:
            summary = {
                k: v / max(int(n_chips), 1) for k, v in lowered_summary.items()
            }
    profile: dict[str, Any] = dict(summary)
    profile["name"] = name
    profile["lower_time_s"] = round(t1 - t0, 4)
    profile["compile_time_s"] = round(t2 - t1, 4)
    profile["memory"] = memory_summary(compiled)
    try:
        parsed = parse_hlo_ops(compiled.as_text())
    except Exception as exc:  # noqa: BLE001
        logger.debug("HLO parse for %s failed: %s", name, exc)
        parsed = {"ops": {}, "totals": {}, "collective_bytes": 0.0}
    hlo_collective = parsed.get("collective_bytes", 0.0)
    profile["collective_bytes"] = max(float(collective_bytes), hlo_collective)
    profile["top_ops"] = top_ops(parsed, peaks, k=top_k)
    profile["roofline"] = classify_roofline(
        flops=profile["flops"],
        bytes_accessed=profile["bytes_accessed"],
        collective_bytes=profile["collective_bytes"],
        peaks=peaks,
    )
    return profile


# --------------------------------------------------------------------------
# perf_attribution block (report.json / gauges)
# --------------------------------------------------------------------------

# Documented reconciliation tolerance: analytical(XLA)/measured(PaLM-6N)
# MFU ratio outside this band flags a flop-model mismatch in the report.
MFU_RECONCILE_BAND = (0.5, 2.0)


def build_perf_attribution(
    *,
    executables: Iterable[Mapping[str, Any]],
    peaks: Mapping[str, float],
    n_chips: int = 1,
    step_time_ms: float | None = None,
    tokens_per_step: float | None = None,
    palm_flops_per_token: float | None = None,
    measured_mfu: float | None = None,
    collective_bytes: float = 0.0,
    span_totals: Mapping[str, Mapping[str, float]] | None = None,
    steps: int | None = None,
) -> dict[str, Any]:
    """Assemble the ``perf_attribution`` report block.

    ``executables`` are per-executable cost dicts from either profiling
    tier; the primary (first) one — the train step for a fit — drives the
    MFU reconciliation and the step-time split.  All cost figures are
    PER-DEVICE: under SPMD partitioning ``cost_analysis()`` describes the
    per-shard module that each chip actually dispatches.
    ``tokens_per_step`` is the global figure; it divides by ``n_chips``
    wherever it meets a cost figure.
    """
    n_chips = max(int(n_chips), 1)
    rows: list[dict[str, Any]] = []
    for exe in executables:
        if not exe:
            continue
        row = dict(exe)
        row.setdefault("collective_bytes", collective_bytes if not rows else 0.0)
        if "roofline" not in row:
            row["roofline"] = classify_roofline(
                flops=row.get("flops", 0.0),
                bytes_accessed=row.get("bytes_accessed", 0.0),
                collective_bytes=row["collective_bytes"],
                peaks=peaks,
            )
        rows.append(row)

    block: dict[str, Any] = {
        "device_kind": peaks.get("device_kind", "unknown"),
        "n_chips": n_chips,
        "peaks": {key: float(peaks.get(key, 0.0)) for key in _PEAK_KEYS},
        "executables": rows,
    }

    primary = rows[0] if rows else None
    if primary is not None and step_time_ms and step_time_ms > 0:
        step_s = step_time_ms / 1e3
        flops_per_chip = primary.get("flops", 0.0)
        analytical_mfu = flops_per_chip / step_s / max(peaks.get("peak_flops", 1.0), 1.0)
        mfu_block: dict[str, Any] = {"analytical": round(analytical_mfu, 6)}
        if measured_mfu is not None:
            mfu_block["measured"] = round(float(measured_mfu), 6)
        if tokens_per_step and palm_flops_per_token:
            # Deterministic form: step time cancels out of the ratio.
            # Per-device flops over per-device tokens — the same "one
            # chip" frame utils/hw.py mfu() measures in.
            ratio = flops_per_chip / (
                float(tokens_per_step) / n_chips * float(palm_flops_per_token)
            )
            mfu_block["ratio_analytical_over_measured"] = round(ratio, 4)
            lo, hi = MFU_RECONCILE_BAND
            mfu_block["reconciled"] = bool(lo <= ratio <= hi)
            mfu_block["tolerance_band"] = [lo, hi]
        block["mfu"] = mfu_block

        roof = primary.get("roofline") or classify_roofline(
            flops=primary.get("flops", 0.0),
            bytes_accessed=primary.get("bytes_accessed", 0.0),
            collective_bytes=primary.get("collective_bytes", 0.0),
            peaks=peaks,
        )
        analytical = roof.get("analytical_ms", {})
        compute_ms = analytical.get("compute", 0.0)
        comms_ms = analytical.get("comms", 0.0)
        host_ms = 0.0
        if span_totals and steps:
            for span in ("data_wait", "host_dispatch"):
                entry = span_totals.get(span)
                if entry:
                    host_ms += entry.get("total_ms", 0.0) / max(steps, 1)
        gap_ms = max(0.0, step_time_ms - compute_ms - comms_ms - host_ms)
        block["step_time_split_ms"] = {
            "step": round(step_time_ms, 3),
            "analytical_compute": round(compute_ms, 3),
            "analytical_collective": round(comms_ms, 3),
            "measured_host": round(host_ms, 3),
            "unattributed_gap": round(gap_ms, 3),
        }
    return block


def attribution_gauges(block: Mapping[str, Any]) -> dict[str, float]:
    """Flatten a perf_attribution block into ``perf/*`` registry gauges
    (rendered as ``llmtrain_perf_*`` on /metrics)."""
    gauges: dict[str, float] = {}
    rows = block.get("executables") or []
    if rows:
        primary = rows[0]
        gauges["perf/flops_per_step"] = float(primary.get("flops", 0.0))
        gauges["perf/bytes_per_step"] = float(primary.get("bytes_accessed", 0.0))
        gauges["perf/collective_bytes_per_step"] = float(
            primary.get("collective_bytes", 0.0)
        )
        roof = primary.get("roofline") or {}
        gauges["perf/arithmetic_intensity"] = float(
            roof.get("arithmetic_intensity", 0.0)
        )
        classes = {"compute": 0.0, "memory": 1.0, "comms": 2.0}
        gauges["perf/roofline_class"] = classes.get(roof.get("class", ""), -1.0)
    mfu_block = block.get("mfu") or {}
    if "analytical" in mfu_block:
        gauges["perf/mfu_analytical"] = float(mfu_block["analytical"])
    if "ratio_analytical_over_measured" in mfu_block:
        gauges["perf/mfu_reconcile_ratio"] = float(
            mfu_block["ratio_analytical_over_measured"]
        )
    split = block.get("step_time_split_ms") or {}
    for key, value in split.items():
        gauges[f"perf/step_{key}_ms"] = float(value)
    return gauges


def render_top_ops_markdown(rows: Iterable[Mapping[str, Any]]) -> list[str]:
    """Markdown table lines for a top-ops list (report.md / profile CLI)."""
    lines = [
        "| op | count | flops | bytes | flops% | bytes% | class |",
        "|---|---|---|---|---|---|---|",
    ]
    for row in rows:
        lines.append(
            "| {op} | {count} | {flops:.3g} | {bytes_accessed:.3g} "
            "| {fp:.1f}% | {bp:.1f}% | {cls} |".format(
                op=row.get("op", "?"),
                count=row.get("count", 0),
                flops=row.get("flops", 0.0),
                bytes_accessed=row.get("bytes_accessed", 0.0),
                fp=100.0 * row.get("flops_frac", 0.0),
                bp=100.0 * row.get("bytes_frac", 0.0),
                cls=row.get("class", "?"),
            )
        )
    return lines


__all__ = [
    "MFU_RECONCILE_BAND",
    "resolve_peaks",
    "normalize_cost",
    "cost_summary",
    "memory_summary",
    "parse_hlo_ops",
    "top_ops",
    "classify_roofline",
    "gradient_collective_bytes",
    "lower_cost_profile",
    "aot_profile",
    "build_perf_attribution",
    "attribution_gauges",
    "render_top_ops_markdown",
]
