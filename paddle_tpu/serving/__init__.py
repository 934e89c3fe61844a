"""``paddle_tpu.serving`` — continuous-batching inference over the
compiled decode path.

The "millions of users" layer (ROADMAP item 1): the repo's compiled
decode machinery (``FusedMultiTransformer`` stacked-cache steps, the
programs ``benchmarks/bench_generation.py`` builds) decodes ONE sequence
per program; serving throughput is batch × per-token rate, so this
package multiplies the missing factor. Its pieces:

* :mod:`~paddle_tpu.serving.kv_cache` — a slot-paged KV cache: a
  preallocated page pool, per-slot page tables, and an int8 leg with
  per-page absmax scales (``ServingConfig.kv_dtype``: ``native | bf16 |
  int8``), reusing the q8 absmax grid the optimizer state already uses.
  On the paged-attention kernel tier (``ServingConfig.paged_attention``,
  ISSUE 13) the decode step consumes the pool DIRECTLY through a
  :class:`PagedDecodeCache` view — live pages stream through the Pallas
  kernel in ``ops/paged_attention.py`` and the dense stacked cache never
  exists in the decode program. Since ISSUE 17 the pool also does
  refcounted copy-on-write prefix sharing
  (``ServingConfig.prefix_sharing``): fully-prompt pages are
  published under page-aligned chain digests, an admission whose prompt
  prefix is resident maps those pages read-only and prefills only the
  unshared tail, ``free()`` decrements instead of releasing shared
  pages (double frees raise + count
  ``serving.kv.double_free_total``), and refcount-0 published pages
  park on an idle LRU reclaimed only under allocation pressure.
* :mod:`~paddle_tpu.serving.scheduler` — the bounded request queue and
  iteration-level admission policies (FIFO, prefill-token budget).
* :mod:`~paddle_tpu.serving.programs` — the compiled programs and the one
  place that knows how a call to one is laid out: a decode program per
  batch bucket ({1, 4, 16}), the full prefill, a tail prefill per shared
  prefix length; which decode tier they run (the platform decides under
  ``auto``), donation and adoption of the pools, warm-up. No environment
  variable selects a tier, sharing or the KV dtype: ``ServingConfig``'s
  fields do, and under ``auto`` the code decides from what it observes.
* :mod:`~paddle_tpu.serving.engine` — the step loop over those programs:
  one decode step in flight ahead of the host's read, admission via
  prefill-into-slot at step boundaries, per-slot eviction on
  EOS/length/cancel, ``observability`` metrics and ``resilience`` fault
  seams (``serving.step`` / ``serving.admit`` / ``serving.watchdog`` /
  ``serving.drain``), per-request deadlines with queue-wait load
  shedding, bounded prefill replay after unrecoverable step faults, and
  ``stop(drain=True)`` graceful shutdown.
* :class:`StepWatchdog` (:mod:`paddle_tpu.resilience.watchdog`, shared
  with the training supervisor; ``PADDLE_TPU_SERVING_WATCHDOG_S``): a hung
  compiled step is classified, counted, and its slots recovered instead
  of wedging the engine forever.

Quick start (see README "Serving")::

    from paddle_tpu import serving

    cfg = serving.ServingConfig(num_layers=L, num_heads=H, head_dim=D,
                                max_len=1024, max_batch=16)
    eng = serving.Engine(prefill_fn, step_fn, cfg).warmup()
    fut = eng.submit(serving.GenerationRequest(prompt, max_new_tokens=64))
    eng.start()                  # or eng.run() to drain synchronously
    print(fut.result().tokens)
"""

from .kv_cache import (KVCacheConfig, PagedDecodeCache,  # noqa: F401
                       PagedKVCache)
from .scheduler import (DeadlineExceeded, GenerationRequest,  # noqa: F401
                        GenerationResult, QueueFull, Scheduler)
from .engine import (DrainTimeout, Engine, EngineStopped,  # noqa: F401
                     ServingConfig)
from ..resilience.watchdog import (StepWatchdog,  # noqa: F401
                                   WatchdogTimeout)
from .router import (NoHealthyReplica, Replica, Router,  # noqa: F401
                     RouterConfig)
from .http import FrontDoor, retry_after_s, status_for  # noqa: F401
from .fleet import (FleetSupervisor, FleetWorkerLost,  # noqa: F401
                    FleetWorkerSpec, ProcessReplica, RemoteEngine)

__all__ = [
    "KVCacheConfig", "PagedKVCache", "PagedDecodeCache",
    "GenerationRequest", "GenerationResult", "QueueFull", "Scheduler",
    "DeadlineExceeded", "Engine", "ServingConfig",
    "EngineStopped", "DrainTimeout", "StepWatchdog", "WatchdogTimeout",
    "NoHealthyReplica", "Replica", "Router", "RouterConfig",
    "FrontDoor", "status_for", "retry_after_s",
    "FleetSupervisor", "FleetWorkerSpec", "FleetWorkerLost",
    "ProcessReplica", "RemoteEngine",
]
