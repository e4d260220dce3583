"""Profiling: ``jax.profiler`` capture, the compiled stage kernel's op map,
and HLO FLOP/byte estimates.

  * ``profiler_trace(logdir)`` wraps a run in ``jax.profiler.trace`` so the
    XLA-level timeline lands in TensorBoard format
    (``ObsSpec.jax_profiler_dir``);
  * ``StageProfiler`` (wired whenever a recorder is) keeps each stage-kernel
    executable the engine runs, and ``stage_op_names`` maps its compiled
    HLO instructions, the names a device trace's ops carry, to their
    ``op_name`` metadata: the ``jax.named_scope``s ``bet.batch``,
    ``bet.forward`` (its backward is ``transpose(jvp(bet.forward))``),
    ``bet.optimizer`` and ``bet.probe`` say which part of the step an op
    belongs to;
  * ``hlo_cost`` estimates FLOPs and bytes of one compiled function, and
    ``seed_kernel_costs`` applies it to the seed pallas-kernel oracles
    (benchmarks/roofline.py plots these).

Deliberately NOT imported by ``repro.obs.__init__``: this module needs jax;
events/metrics/report stay stdlib-importable.
"""
from __future__ import annotations

import collections
import contextlib
import re
import time

import jax
import jax.numpy as jnp

from ..launch import hlo

# TPU v5e roofline constants, per chip (same as launch/dryrun.py)
PEAK_FLOPS = 197e12        # bf16 FLOP/s
HBM_BW = 819e9             # bytes/s


def profiler_trace(logdir):
    """``jax.profiler.trace`` when a log dir is given, no-op otherwise."""
    if not logdir:
        return contextlib.nullcontext()
    return jax.profiler.trace(str(logdir))


def _roofline(flops: float, nbytes: float) -> dict:
    compute_s = flops / PEAK_FLOPS
    memory_s = nbytes / HBM_BW
    return {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "roofline_us": max(compute_s, memory_s) * 1e6,
        "bottleneck": "memory" if memory_s > compute_s else "compute",
        "intensity_flops_per_byte": flops / nbytes if nbytes else 0.0,
    }


def cost_from_compiled(compiled) -> dict:
    """FLOP/byte estimates for a compiled computation: XLA's own
    ``cost_analysis`` plus the repo's HLO-text analyzer as fallback and
    collective detail."""
    out = {"flops": 0.0, "bytes": 0.0}
    try:
        raw = hlo.raw_cost_analysis(compiled)
        out["flops"] = float(raw.get("flops", 0.0) or 0.0)
        out["bytes"] = float(raw.get("bytes accessed", 0.0) or 0.0)
    except Exception:
        pass
    try:
        an = hlo.analyze(compiled.as_text())
        out["hlo_flops"] = float(an.get("flops", 0.0))
        out["hlo_traffic_bytes"] = float(an.get("traffic_bytes", 0.0))
        out["hlo_wire_bytes"] = float(an.get("wire_bytes", 0.0))
        if out["flops"] <= 0.0:
            out["flops"] = out["hlo_flops"]
        if out["bytes"] <= 0.0:
            out["bytes"] = out["hlo_traffic_bytes"]
    except Exception:
        pass
    return out


def hlo_cost(fn, *args, static_argnames=(), **kwargs) -> dict:
    """Lower + compile ``fn`` on ``args`` and estimate FLOPs/bytes plus the
    roofline terms.  Costs the compile — call once per shape."""
    compiled = jax.jit(fn, static_argnames=tuple(static_argnames)) \
        .lower(*args, **kwargs).compile()
    cost = cost_from_compiled(compiled)
    cost.update(_roofline(cost["flops"], cost["bytes"]))
    return cost


# ---------------------------------------------------------- seed kernel costs
def _seed_kernel_cases() -> dict:
    """(fn, args) per seed pallas kernel, over the reference oracles at
    bench-representative small shapes (kernels/ref.py signatures)."""
    from ..kernels import ref

    f32 = jnp.float32
    X = jnp.ones((256, 64), f32)
    y = jnp.ones((256,), f32)
    w = jnp.ones((64,), f32)
    q = jnp.ones((1, 2, 128, 64), f32)
    u = jnp.ones((1, 64, 32), f32)
    bc = jnp.ones((1, 64, 16), f32)
    A_log = jnp.zeros((32, 16), f32)
    D = jnp.ones((32,), f32)
    ab = jnp.ones((1, 64, 32), f32)
    return {
        "linear_forward": (ref.linear_forward, (X, w)),
        "linear_value_grad": (ref.linear_value_grad, (X, y, w)),
        "flash_attention": (ref.flash_attention, (q, q, q)),
        "ssm_scan": (ref.ssm_scan, (u, u, bc, bc, A_log, D)),
        "rglru_scan": (ref.rglru_scan, (ab, ab)),
    }


def seed_kernel_costs() -> dict:
    """Per-kernel FLOPs/bytes/roofline for the seed pallas kernels.  Kernels
    that fail to lower report an ``error`` instead of aborting the sweep."""
    out = {}
    for name, (fn, args) in _seed_kernel_cases().items():
        try:
            out[name] = hlo_cost(fn, *args)
        except Exception as exc:
            out[name] = {"error": f"{type(exc).__name__}: {exc}"}
    return out


# ------------------------------------------------------------ stage profiling
#: Stage-kernel executables observed while a recorder was wired, oldest
#: first: signature -> [Lowered, perf_counter time last observed].  Like
#: ``ops.CALLS``, one registry per process; the oldest entry goes once it
#: holds ``MAX_KERNELS``.
STAGE_KERNELS: collections.OrderedDict = collections.OrderedDict()
MAX_KERNELS = 16

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPERAND = re.compile(r"(?<![=\w])%([\w.\-]+)")


def _signature(kernel, args, kwargs) -> tuple:
    leaves, tree = jax.tree_util.tree_flatten(args)
    return (id(kernel), tree, tuple(sorted(kwargs.items())),
            tuple((jnp.shape(x), str(jnp.result_type(x)),
                   getattr(x, "sharding", None)) for x in leaves))


def hlo_op_names(hlo_text: str) -> dict:
    """``{HLO instruction name: (op_name, ...)}`` of one compiled module's
    text.  An instruction has its own ``op_name`` metadata; one without
    takes those of the instructions in its fused computation (a fusion),
    else of its operands, else of its users: the compiler's async copies
    and slices into fast memory are named by the op they load from or
    feed."""
    own, calls, operands, body, comp = {}, {}, {}, {}, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = body.setdefault(m.group(1), [])
            continue
        m = _INSTRUCTION.match(line)
        if m is None or comp is None:
            continue
        name = m.group(1)
        comp.append(name)
        op = _OP_NAME.search(line)
        own[name] = (op.group(1),) if op else ()
        c = _CALLS.search(line)
        if c:
            calls[name] = c.group(1)
        operands[name] = [o for o in _OPERAND.findall(line, m.end())
                          if o in own]

    def fused(inst: str) -> tuple:
        out = list(own[inst])
        for i in () if out else body.get(calls.get(inst), ()):
            out.extend(n for n in fused(i) if n not in out)
        return tuple(out)

    names = {i: fused(i) for i in own}
    users: dict = {}
    for i, ops in operands.items():
        for o in ops:
            users.setdefault(o, []).append(i)
    # the text lists operands before their users, so one pass each way
    for order, links in ((list(own), operands),
                         (list(reversed(own)), users)):
        for i in order:
            if not names[i]:
                names[i] = tuple(dict.fromkeys(
                    n for j in links.get(i, ()) for n in names[j]))
    return names


def stage_op_names(since: float | None = None) -> list:
    """One ``hlo_op_names`` map per stage-kernel executable observed at or
    after ``since`` (``perf_counter`` time; None: every one).  Built on
    request, never on the hot path: each compile() finds the executable the
    observed call already compiled, so nothing compiles again."""
    return [hlo_op_names(lowered.compile().as_text())
            for lowered, seen in list(STAGE_KERNELS.values())
            if since is None or seen >= since]


class StageProfiler:
    """The engine's hook for reading its compiled stage kernels.  The engine
    calls ``observe`` before a stage's first kernel launch (the kernel
    donates its carries, so after the call they are gone); each new
    (kernel, argument shapes and shardings) is lowered there, which the
    call that follows reuses, and kept in ``STAGE_KERNELS``.  Nothing
    compiles here."""

    def observe(self, info, kernel, args, kwargs) -> None:
        key = _signature(kernel, args, kwargs)
        entry = STAGE_KERNELS.get(key)
        if entry is None:
            entry = STAGE_KERNELS[key] = [kernel.lower(*args, **kwargs), 0.0]
            while len(STAGE_KERNELS) > MAX_KERNELS:
                STAGE_KERNELS.popitem(last=False)
        entry[1] = time.perf_counter()
