"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

What a v5e trace holds (recorded by ``bench/harness.py``; JAX 0.9, libtpu
0.0.34): a host plane ``/host:CPU`` whose Python thread carries the
benchmark's own spans (``bench.traced``, the stretch; ``bench.submit``,
``bench.tick``, ``bench.log``; ``bench.prefill`` with its ``bucket`` and
``bench.decode`` around the engine's step dispatches), and one plane per
chip, ``/device:TPU:<n>``, with two lines: ``XLA Modules``, one event per
program execution named ``jit_<function>(<hash>)``, and ``XLA Ops``, one
event per HLO instruction execution named by its HLO text
``%<instruction> = <shape> <op>(...)``. A ``while`` op spans the ops of its
body, which appear as events of their own, once per iteration. Host and
device events share one clock.

The reduction, over the stretch only:

- busy time: the union of the op intervals on each chip; the idle gaps are
  its holes, each labelled with the innermost benchmark span covering its
  midpoint (what the host was doing);
- each execution of a step program (``PROGRAMS``): its device time and the
  time of the weight GEMMs it ran. Those are the Pallas custom calls named
  after the kernels' entry points (``GEMM_KERNELS``), plus the ops that
  stage each kernel's weight operand (its second): XLA copies each layer's
  weight slice, or pads the unembedding, into a VMEM buffer (its layout
  carries memory space ``S(1)``, as the op names in the trace show), which
  the kernel then reads, so the kernel's own time leaves out the weights'
  trip from HBM. Both times are kept apart too (``kernel_s``,
  ``staging_s``). A step runs ``7 * layers + 1`` GEMMs (q, k, v, o, gate,
  up, down in every layer, then the unembedding); where an execution runs
  another number, its GEMM time cannot be matched to the work
  ``bench/work.py`` counts, and the program's ``gemm_s`` is None with the
  reason in ``gemm_mismatch``, which the result line carries;
- the rows each execution carried, from the host spans in dispatch order;
- the breakdown: the device ops that took most time, by program and
  instruction (``while`` and call ops left out: their bodies are counted),
  and the longest idle gaps.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

# module-name prefix of each step program (jit of the engine's functions)
PROGRAMS = {"prefill": "jit_prefill_chunk", "decode": "jit_decode"}
# instruction-name stems of the Pallas GEMM custom calls
# (kernels/matmul.py ``matmul``, kernels/decode_matvec.py ``decode_matvec``)
GEMM_KERNELS = ("matmul", "decode_matvec")
PALLAS = 'custom_call_target="tpu_custom_call"'
GEMMS_PER_LAYER = 7
CONTAINERS = ("while", "conditional", "call")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
_INSTR = re.compile(r"^%(\S+) = (\S+)")


class TraceError(RuntimeError):
    """The trace cannot be reduced as the benchmark expects."""


@dataclasses.dataclass
class ProgramTime:
    count: int = 0
    device_s: float = 0.0                   # module execution time
    gemm_s: float | None = 0.0    # GEMM kernels + weight staging; None: unmatched
    kernel_s: float = 0.0                   # the GEMM kernels alone
    staging_s: float = 0.0                  # the ops that stage their weights
    rows: list = dataclasses.field(default_factory=list)  # (rows, logit rows)
    gemm_mismatch: str | None = None


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                           # mean over chips
    programs: dict                          # kind -> ProgramTime
    breakdown: dict


def find_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise TraceError(f"expected one .xplane.pb under {directory}, "
                         f"found {len(found)}")
    return found[0]


def union(intervals) -> list[list[float]]:
    """Merged, sorted [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def holes(merged, w0: float, w1: float) -> list[tuple[float, float]]:
    """The parts of [w0, w1] that ``merged`` (from ``union``) leaves free."""
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def instruction(name: str) -> tuple[str, str]:
    """(instruction name, result shape without layout) of an op event."""
    m = _INSTR.match(name)
    if not m:
        return name, ""
    shape = m.group(2)
    shape = "(tuple)" if shape.startswith("(") else re.sub(r"\{.*", "", shape)
    return m.group(1), shape


def _stem(instr: str) -> str:
    return re.sub(r"\.\d+$", "", instr)


def is_gemm(name: str) -> bool:
    return PALLAS in name and _stem(instruction(name)[0]) in GEMM_KERNELS


def weight_operand(name: str) -> str | None:
    """The instruction that produces a GEMM custom call's second operand."""
    args = re.findall(r"%([^\s,)]+)", name.split("custom-call(", 1)[-1])
    return args[1] if len(args) > 1 else None


def _program(module: str) -> str:
    return re.sub(r"\(.*", "", module).removeprefix("jit_")


def reduce(path: str, *, layers: int, slots: int) -> TraceSummary:
    """Reduce the trace at ``path`` of a dense model with ``layers``
    layers served over ``slots`` lanes."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host = [(ev.name, ev.start_ns, ev.duration_ns, ev)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("bench.")]
    traced = [ev for ev in host if ev[0] == "bench.traced"]
    if len(traced) != 1:
        raise TraceError(f"expected one bench.traced span, found {len(traced)}")
    w0, w1 = traced[0][1], traced[0][1] + traced[0][2]
    steps = sorted((ev for ev in host
                    if ev[0] in ("bench.prefill", "bench.decode")
                    and w0 <= ev[1] <= w1), key=lambda ev: ev[1])
    buckets = [int(dict(ev[3].stats)["bucket"]) for ev in steps
               if ev[0] == "bench.prefill"]
    n_decode = sum(1 for ev in steps if ev[0] == "bench.decode")

    devices = [p for p in pd.planes if DEVICE_PLANE.match(p.name)]
    if not devices:
        raise TraceError("no TPU device plane in the trace")
    busy_total, per_op, gaps_all = 0.0, {}, []
    programs = {k: ProgramTime() for k in PROGRAMS}
    want = GEMMS_PER_LAYER * layers + 1
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines or MODULES_LINE not in lines:
            raise TraceError(f"{plane.name} lacks the {OPS_LINE!r} or "
                             f"{MODULES_LINE!r} line: {sorted(lines)}")
        ops = [(e.name, e.start_ns, e.duration_ns)
               for e in lines[OPS_LINE].events
               if e.start_ns < w1 and e.start_ns + e.duration_ns > w0]
        merged = union((max(s, w0), min(s + d, w1)) for _, s, d in ops)
        busy_total += sum(e - s for s, e in merged) * 1e-9
        gaps_all.extend(holes(merged, w0, w1))

        mods = sorted((e.start_ns, e.duration_ns, e.name)
                      for e in lines[MODULES_LINE].events
                      if w0 <= e.start_ns and e.start_ns + e.duration_ns <= w1)
        starts = [m[0] for m in mods]
        inside = {i: [] for i in range(len(mods))}
        for name, s, d in ops:
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s + d > mods[i][0] + mods[i][1]:
                continue
            inside[i].append((name, d))
            instr, shape = instruction(name)
            if _stem(instr) in CONTAINERS:
                continue
            label = f"{_program(mods[i][2])}/{instr} {shape}".strip()
            per_op[label] = per_op.get(label, 0.0) + d * 1e-9

        seen = {k: 0 for k in PROGRAMS}
        for i, (s, d, name) in enumerate(mods):
            kind = next((k for k, p in PROGRAMS.items()
                         if name.startswith(p + "(")), None)
            if kind is None:
                continue
            prog = programs[kind]
            gemms = [(on, od) for on, od in inside[i] if is_gemm(on)]
            staging = {weight_operand(on) for on, _ in gemms} - {None}
            prog.count += 1
            prog.device_s += d * 1e-9
            if len(gemms) != want and prog.gemm_mismatch is None:
                prog.gemm_mismatch = (
                    f"{name} ran {len(gemms)} GEMM kernels, expected {want} "
                    f"({GEMMS_PER_LAYER} x {layers} layers + the unembedding)")
            kernel = 1e-9 * sum(od for _, od in gemms)
            staged = 1e-9 * sum(od for on, od in inside[i]
                                if instruction(on)[0] in staging)
            prog.kernel_s += kernel
            prog.staging_s += staged
            prog.gemm_s += kernel + staged
            if kind == "prefill":
                if seen[kind] >= len(buckets):
                    raise TraceError("more prefill executions than "
                                     "bench.prefill spans")
                prog.rows.append((buckets[seen[kind]], 1))
            else:
                prog.rows.append((slots, slots))
            seen[kind] += 1
        if seen["prefill"] != len(buckets) or seen["decode"] != n_decode:
            raise TraceError(
                f"{plane.name}: {seen} step executions in the traced "
                f"stretch, but the host dispatched {len(buckets)} prefill "
                f"and {n_decode} decode steps")

    for prog in programs.values():
        if prog.gemm_mismatch:
            prog.gemm_s = None
    n = len(devices)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(gaps_all, key=lambda g: g[0] - g[1])[:TOP]
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy_total / n, programs=programs,
        breakdown={
            "device_ops": [[k, v / n] for k, v in top_ops],
            "idle_gaps": [[_label(host, g), (g[1] - g[0]) * 1e-9]
                          for g in gaps],
        })


def _label(host, gap) -> str:
    """The innermost benchmark span covering the gap's midpoint."""
    mid = (gap[0] + gap[1]) / 2
    inside = [ev for ev in host if ev[0] != "bench.traced"
              and ev[1] <= mid <= ev[1] + ev[2]]
    if not inside:
        return "outside any bench span"
    return min(inside, key=lambda ev: ev[2])[0]


def annotate_steps(engine) -> None:
    """Wrap the engine's step programs in benchmark host spans (traced
    runs), so the reduction knows the rows of each execution."""
    import jax

    art = engine.art
    prefill, decode = art.prefill_fn, art.decode_fn

    def prefill_fn(params, state, chunk, *rest):
        with jax.profiler.TraceAnnotation("bench.prefill",
                                          bucket=int(chunk.shape[1])):
            return prefill(params, state, chunk, *rest)

    def decode_fn(*args):
        with jax.profiler.TraceAnnotation("bench.decode"):
            return decode(*args)

    art.prefill_fn, art.decode_fn = prefill_fn, decode_fn
