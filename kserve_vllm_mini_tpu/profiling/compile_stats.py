"""Compile-stats capture: wrap ``lower().compile()`` and keep the numbers.

XLA already computes an analytic cost model (FLOPs, bytes accessed) and a
buffer-assignment memory estimate for every executable it builds; the repo
used to throw both away. This module makes them first-class run metrics:

- ``capture_compile_stats(jfn, *args)`` — explicit AOT compile of a jitted
  callable, timed, with ``cost_analysis()`` / ``memory_analysis()`` / HLO
  op histogram extracted into a ``CompileStats`` record. The compiled
  executable is returned so callers run exactly what was measured.
- ``InstrumentedJit`` — a drop-in wrapper around a jitted callable: the
  first call per abstract signature compiles explicitly (stats land in a
  ``CompileRecorder``), later calls hit the cached executable. A compile
  error raises, once, to the caller: a step that cannot compile has no
  executable to serve with, and retrying it through plain ``jit`` would
  compile the same program a second time and turn the stats off.
- ``CompileRecorder`` — thread-safe accumulator the engine exports through
  ``snapshot_stats`` / ``/metrics`` (docs/API.md metrics table).

Everything here is a count or a compiler estimate — compile wall time is
set-up time, never a device metric (docs/PROFILING.md).
"""

from __future__ import annotations

import math
import re
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable

# `%dot.3 = f32[64,64]{1,0} dot(...)` / `ROOT %t = (f32[2]{0}) tuple(...)`:
# the opcode is the first lowercase identifier directly followed by "(" on
# the right-hand side of the assignment (types carry brackets, not parens).
_HLO_OPCODE = re.compile(r"([a-z][a-z0-9_\-]*)\(")
_TOP_OPS = 16  # histogram cap: top-N opcodes, remainder folded into "other"
# `%copy.69 = s8[1,4096,4096]{1,2,0:T(8,128)(4,1)S(1)} copy(%slice)`: a copy
# (not `copy-start`/`copy-done`, whose results are tuples) whose result is ONE
# layer of a stacked integer weight leaf — rank 3, leading 1. K/V and
# activations are rank 4-5 or not integers; scales and norms are floats.
_WEIGHT_COPY = re.compile(r" = (s8|u8|s4)\[1,(\d+),(\d+)\]\S* copy\(")
_WEIGHT_COPY_MIN_BYTES = 64 * 1024  # below this it is no projection's weight
# `%cache__lin_state__.1 = f32[24,16,30,96,192]{4,3,2,1,0:T(8,128)} parameter(47)`
# in the entry computation: a float32 leaf of rank 5 is a recurrent state's
# layer stack (K/V stacks are bf16 or int8, their scales rank 4). What is then
# looked for is that stack, or one layer of it (`f32[1,16,30,96,192]`), as the
# result of a copy, a slice (static where the walk is unrolled, dynamic in a
# loop, `slice-done` where it runs asynchronously) or a dynamic-update-slice,
# fused or not.
_F32_RANK5 = re.compile(
    r" = f32\[(\d+),(\d+),(\d+),(\d+),(\d+)\]\S* "
    r"(parameter|copy|slice|slice-done|dynamic-slice|dynamic-update-slice)\(")


@dataclass
class CompileStats:
    """One executable's compile-time facts (all analytic — no device time)."""

    label: str
    compile_wall_s: float
    flops: float                  # cost-model FLOPs per invocation
    bytes_accessed: float         # cost-model HBM traffic per invocation
    peak_bytes: int               # buffer-assignment peak estimate (args+temp+out+code)
    argument_bytes: int
    output_bytes: int
    temp_bytes: int
    generated_code_bytes: int
    hlo_ops: dict[str, int] = field(default_factory=dict)
    # custom calls whose target is Mosaic: 0 means no Pallas kernel was
    # compiled into this executable (the opcode histogram cannot tell a
    # Mosaic call from XLA's own custom calls)
    mosaic_calls: int = 0
    # bytes of per-layer integer weight slices the program copies (a
    # transposition XLA put in front of a matmul: the weight is then read,
    # written and read again every step); 0 = every weight is multiplied
    # where it lies in its stack
    weight_copy_bytes: int = 0
    # bytes of a recurrent state's layers the program slices out of their
    # float32 stack, writes back into it or copies, outside a Mosaic call:
    # each is a pass over a layer's state beyond the kernel's read and
    # write; 0 = the state is updated where it lies, by a kernel or not at all
    state_copy_bytes: int = 0

    def to_dict(self) -> dict[str, Any]:
        return {
            "label": self.label,
            "compile_wall_s": round(self.compile_wall_s, 4),
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "peak_bytes": self.peak_bytes,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "generated_code_bytes": self.generated_code_bytes,
            "hlo_ops": dict(self.hlo_ops),
            "mosaic_calls": self.mosaic_calls,
            "weight_copy_bytes": self.weight_copy_bytes,
            "state_copy_bytes": self.state_copy_bytes,
        }


def hlo_op_histogram(hlo_text: str, top: int = _TOP_OPS) -> dict[str, int]:
    """Opcode -> instruction count over an HLO module's ``as_text()`` dump.

    Keeps the ``top`` most frequent opcodes and folds the tail into
    ``other`` so the histogram stays artifact-sized for big modules."""
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        eq = line.find(" = ")
        if eq < 0:
            continue
        m = _HLO_OPCODE.search(line, eq + 3)
        if m:
            counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    if len(counts) <= top:
        return counts
    ranked = sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))
    out = dict(ranked[:top])
    out["other"] = sum(c for _, c in ranked[top:])
    return out


def weight_copy_bytes(hlo_text: str) -> int:
    """Summed result bytes of the ``copy`` instructions in an optimized HLO
    module that produce one layer's slice of a stacked integer weight
    (``s8``/``u8``/``s4`` ``[1, in, out]``, at least 64 KiB). Each
    instruction counts once, a loop body's too: the number says which
    weights are copied, not how often. (The int8 activations of a
    ``quant_mode="w8a8"`` prefill piece, ``s8[1, T, d]``, have a weight's
    shape: read a non-zero there against the HLO text.)"""
    total = 0
    for dtype, rows, cols in _WEIGHT_COPY.findall(hlo_text):
        n = int(rows) * int(cols)
        n = n // 2 if dtype == "s4" else n
        if n >= _WEIGHT_COPY_MIN_BYTES:
            total += n
    return total


def state_copy_bytes(hlo_text: str) -> int:
    """Summed bytes of the layers of a recurrent-state stack that an
    optimized HLO module slices out (``slice``, ``dynamic-slice``), writes back
    (``dynamic-update-slice``: one layer counted) or copies (``copy``: its
    result) outside a Mosaic call. The stacks are the entry computation's
    float32 parameters of rank 5 (``lin_state``, ``ssm_state``). Each
    instruction counts once, a loop body's and a fusion's too, like
    ``weight_copy_bytes``: the number says which state is moved an extra
    time, not how often. The jnp update of models/linear_attention.py
    reads 9 x one layer over a period's three linear layers (each layer's
    two fusions slice it out, one writes it back); ``kvmini_linear_decode``
    (ops/linear_decode.py) updates the stack in place and reads 0."""
    found = [(tuple(int(d) for d in m.groups()[:5]), m.group(6), m.start())
             for m in _F32_RANK5.finditer(hlo_text)]
    entry = hlo_text.find("\nENTRY ")
    stacks = {dims for dims, op, at in found if op == "parameter" and 0 <= entry < at}
    total = 0
    for dims, op, _ in found:
        if op == "parameter" or not any(
                dims[1:] == st[1:] and dims[0] in (1, st[0]) for st in stacks):
            continue
        layer = 4 * math.prod(dims[1:])
        total += layer * dims[0] if op == "copy" else layer
    return total


def extract_compile_stats(
    compiled: Any, compile_wall_s: float, label: str = ""
) -> CompileStats:
    """Pull cost/memory/HLO facts out of a ``jax.stages.Compiled``."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ma = compiled.memory_analysis()
    arg = int(ma.argument_size_in_bytes)
    out = int(ma.output_size_in_bytes)
    temp = int(ma.temp_size_in_bytes)
    code = int(ma.generated_code_size_in_bytes)
    hlo_text = compiled.as_text()
    return CompileStats(
        label=label,
        compile_wall_s=compile_wall_s,
        flops=float(ca.get("flops", 0.0) or 0.0),
        bytes_accessed=float(ca.get("bytes accessed", 0.0) or 0.0),
        # aliased (donated) buffers are counted inside argument bytes but
        # reuse their input allocation — subtract so the peak isn't double
        peak_bytes=max(arg + out + temp + code - int(ma.alias_size_in_bytes), 0),
        argument_bytes=arg,
        output_bytes=out,
        temp_bytes=temp,
        generated_code_bytes=code,
        hlo_ops=hlo_op_histogram(hlo_text),
        mosaic_calls=hlo_text.count('custom_call_target="tpu_custom_call"'),
        weight_copy_bytes=weight_copy_bytes(hlo_text),
        state_copy_bytes=state_copy_bytes(hlo_text),
    )


def capture_compile_stats(
    jfn: Any, *args: Any, label: str = "", **kwargs: Any
) -> tuple[Any, CompileStats]:
    """Explicitly ``lower().compile()`` a jitted callable and keep the
    stats. Arguments may be concrete arrays or ``jax.ShapeDtypeStruct``
    trees (abstract lowering compiles the real program without ever
    materializing the weights).

    Returns ``(compiled_executable, stats)``; the executable accepts the
    same (concrete) calling convention as the jitted function, donation
    included."""
    t0 = time.perf_counter()
    compiled = jfn.lower(*args, **kwargs).compile()
    wall = time.perf_counter() - t0
    return compiled, extract_compile_stats(compiled, wall, label=label)


class CompileRecorder:
    """Thread-safe compile-stats accumulator.

    The engine's scheduler thread records; the server's request threads
    read ``snapshot()`` — every access is under the one lock (KVM05x
    discipline), and ``snapshot``/``entries`` return copies."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._entries: list[CompileStats] = []
        self._total_s = 0.0
        self._total_flops = 0.0
        self._total_bytes = 0.0
        self._peak_bytes = 0

    def record(self, stats: CompileStats) -> None:
        with self._lock:
            self._entries.append(stats)
            self._total_s += stats.compile_wall_s
            self._total_flops += stats.flops
            self._total_bytes += stats.bytes_accessed
            self._peak_bytes = max(self._peak_bytes, stats.peak_bytes)

    def snapshot(self) -> dict[str, Any]:
        """Flat totals for ``snapshot_stats`` / ``/metrics``."""
        with self._lock:
            return {
                "compiles": len(self._entries),
                "compile_s": self._total_s,
                "compiled_flops": self._total_flops,
                "compiled_bytes": self._total_bytes,
                "compile_peak_bytes": self._peak_bytes,
            }

    def entries(self) -> list[CompileStats]:
        with self._lock:
            return list(self._entries)


def _signature(args: tuple, kwargs: dict) -> tuple:
    """Abstract signature of a call: tree structure + per-leaf aval.

    Matches jit's own cache key closely enough that one signature maps to
    one executable (shape, dtype, weak_type per leaf — a Python scalar and
    a committed array hash differently, exactly like jit retraces)."""
    import jax
    from jax.api_util import shaped_abstractify

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    sig = []
    for leaf in leaves:
        aval = shaped_abstractify(leaf)
        sig.append((aval.shape, str(aval.dtype), bool(getattr(aval, "weak_type", False))))
    return (treedef, tuple(sig))


class InstrumentedJit:
    """AOT-compiling wrapper around a jitted callable.

    The first call per abstract signature runs ``lower().compile()``
    explicitly (timed, stats into the recorder) and caches the executable;
    later calls dispatch straight to it — one compile total, same donation
    semantics as the wrapped jit. A compile error (a Mosaic lowering
    failure, RESOURCE_EXHAUSTED) propagates: nothing is cached for that
    signature, so every executable that ever ran is in the recorder."""

    def __init__(self, fn: Callable, recorder: CompileRecorder,
                 label: str = "") -> None:
        self._fn = fn
        self._recorder = recorder
        self._label = label or getattr(fn, "__name__", "jit")
        self._exes: dict[tuple, Callable] = {}
        # fast path: an engine step is compiled for exactly ONE signature
        # in almost every run, so once a single executable exists we
        # dispatch to it directly instead of re-deriving the abstract key
        # (a ~300-leaf params flatten per decode dispatch would be real
        # host overhead on the pipelined hot path). A structure/shape
        # mismatch raises during the executable's argument VALIDATION —
        # before any buffer is donated — and drops us back to the keyed
        # path permanently.
        self._sole_exe: Any = None

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        if self._sole_exe is not None:
            try:
                return self._sole_exe(*args, **kwargs)
            except (TypeError, ValueError):
                self._sole_exe = None
        key = _signature(args, kwargs)
        exe = self._exes.get(key)
        if exe is None:
            exe, stats = capture_compile_stats(
                self._fn, *args, label=self._label, **kwargs
            )
            self._recorder.record(stats)
            self._exes[key] = exe
            if len(self._exes) == 1:
                self._sole_exe = exe
        return exe(*args, **kwargs)
