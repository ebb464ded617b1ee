"""kubernetes_tpu — a TPU-native scheduling framework.

Re-implements the capabilities of Kubernetes' kube-scheduler (reference:
tkashem/kubernetes, `pkg/scheduler/`) as a batched pod×node constraint engine
evaluated on-device with JAX/XLA.  The reference's goroutine-parallel Filter and
Score hot loops (`pkg/scheduler/schedule_one.go:591,755`) become vectorized ops
over a device-resident cluster-state tensor; the serialized one-pod-at-a-time
outer loop (`pkg/scheduler/scheduler.go:470`) becomes a `lax.scan` over a pod
batch with sequential-equivalent greedy commits, so an entire batch of pending
pods is scheduled in one device dispatch.

Layering (mirrors SURVEY.md §7):
  api/        — the object model (Pod, Node, affinity, quantities) + test builders
  intern      — string interning: labels/taints/topology values → dense ids
  cache       — host-side authoritative cluster state w/ assume/forget + generations
  snapshot    — device tensor schema + incremental (generation-diff) uploader
  ops/        — vectorized scheduling plugins (filters + scorers)
  engine/     — the jitted batch pass: filter → score → select → commit scan
  queue       — activeQ/backoffQ/unschedulable three-stage scheduling queue
  scheduler   — the driving loop (ScheduleOne-equivalent, batched)
  parallel/   — multi-chip sharding of the node axis (jax.sharding.Mesh)
  perf/       — scheduler_perf-style benchmark harness
"""

import os

import jax

# Score and resource arithmetic is int64 for bit-identical parity with the
# reference's Go int64 math (e.g. leastRequestedScore in
# pkg/scheduler/framework/plugins/noderesources/least_allocated.go:97:
# ((capacity-requested)*MaxNodeScore)/capacity must truncate identically).
# Kubernetes memory quantities are int64 bytes and exceed int32 range.
jax.config.update("jax_enable_x64", True)
# All matmuls in this framework are integer-count/score math cast to f32
# for the MXU (domain tables, selector masks, weighted sums).  The TPU
# default (bfloat16 passes) truncates integers above 256 — a domain holding
# 300 pods would read back as 298/302 and flip exact skew/affinity
# comparisons — so force full-f32 accumulation: counts < 2^24 stay exact.
jax.config.update("jax_default_matmul_precision", "highest")

# Persist XLA compilations across processes: the batch pass compiles once per
# (profile, schema, batch-size) and those shapes are stable run-to-run.  The
# directory is part of the cache key, so it is a fixed place: wherever
# JAX_COMPILATION_CACHE_DIR says (jax reads the variable itself; no directory
# is set in code then), else .jax_cache beside the package.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache",
        ),
    )
# Every program persists, not only those that took over a second: a
# process on a machine that is thrown away (or a serve child of a test)
# otherwise recompiles the dozens of small ones each time it starts, and a
# program near the threshold lands in the cache on some runs and not others.
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

__version__ = "0.1.0"
