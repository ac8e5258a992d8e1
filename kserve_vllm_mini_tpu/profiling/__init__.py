"""Compile-time and memory observability (docs/PROFILING.md).

Layers, importable separately so the JAX-free harness stages never pay the
runtime import:

- ``compile_stats`` — explicit ``lower().compile()`` capture around the
  repo's jitted executables: compile wall time, XLA cost-model FLOPs and
  bytes-accessed, ``memory_analysis`` peak-buffer estimates, and HLO op
  histograms. ``InstrumentedJit`` wraps the engine's compiled steps so a
  serving process accumulates the same stats into ``/metrics``.
- ``headroom`` — analytic HBM estimates for a serving config, the live
  ``memory_stats`` watermarks, and the estimate-vs-observed error.
- ``devices`` — the device-facts table keyed by exact ``device_kind``
  (capacity, roofline peaks, price/TDP row); an unknown TPU is an error.
"""

from kserve_vllm_mini_tpu.profiling.compile_stats import (  # noqa: F401
    CompileRecorder,
    CompileStats,
    InstrumentedJit,
    capture_compile_stats,
    extract_compile_stats,
    hlo_op_histogram,
)
from kserve_vllm_mini_tpu.profiling.headroom import (  # noqa: F401
    HeadroomPlan,
    estimate_serving_bytes,
    plan_admission,
    serving_headroom_plan,
)
