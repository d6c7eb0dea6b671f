"""Kernel profiling hooks: named scopes for the Pallas kernel call sites.

Two complementary annotations, both no-ops in cost when no profiler is
attached:

* `kernel_scope(name)` — wraps a kernel's ops-layer body in
  ``jax.named_scope``, so every HLO op the kernel lowers to carries
  ``repro.kernels/<name>`` metadata. This works *inside* jit (it annotates
  at trace time) and is how XLA profiles / ``jax.profiler`` traces
  attribute device time back to the kernel that produced it. A
  ``jax.profiler.TraceAnnotation`` is layered on: under jit it only
  brackets trace time, but the same ops wrappers are also called
  eagerly (interpret-mode tests, `launch/profile`), where it emits real
  host TraceMe events.
* `annotate(name)` — host-level ``TraceAnnotation`` alone, for timing loops
  that live outside jit (the `repro.launch.profile` rep timer).

`tpu_roofline_us` is the shared roofline-time helper (same formula as
``benchmarks/kernel_bench._tpu_roofline_us``) so profile records price
their flops/bytes against the identical modeled ceiling the calibration
fitter expects.

jax is imported lazily so ``repro.obs`` stays importable (metrics, tracer)
in tooling contexts without jax on the path.
"""
from __future__ import annotations

import contextlib
from typing import Iterator

#: named_scope prefix for every instrumented kernel call site
SCOPE_PREFIX = "repro.kernels"


@contextlib.contextmanager
def kernel_scope(name: str) -> Iterator[None]:
    """Annotate one kernel call site: HLO metadata (named_scope) + host
    TraceMe. Wraps the ops-layer body, inside or outside jit."""
    import jax
    label = f"{SCOPE_PREFIX}/{name}"
    with jax.named_scope(label), jax.profiler.TraceAnnotation(label):
        yield


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Host-level profiler annotation only (timing loops outside jit)."""
    import jax
    with jax.profiler.TraceAnnotation(f"{SCOPE_PREFIX}/{name}"):
        yield


def tpu_roofline_us(flops: float, bytes_moved: float) -> float:
    """Modeled TPU v5e roofline time for one kernel invocation, in us —
    the ceiling the per-kernel duty factor eta is fit against."""
    from repro.core.devices import TPU_V5E
    t = max(flops / (TPU_V5E.peak_flops * TPU_V5E.util),
            bytes_moved / (TPU_V5E.mem_bw * TPU_V5E.util))
    return t * 1e6
