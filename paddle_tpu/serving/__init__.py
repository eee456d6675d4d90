"""Serving: continuous request batching over the Predictor.

``inference.Predictor`` gives one caller a compiled executable;
"millions of users" need the executable AMORTIZED: many concurrent
callers, each with their own small, oddly-shaped request, served by a
bounded set of warm executables. This package is that layer:

* ``server.BatchingServer`` — a request queue plus a background
  dispatch loop that coalesces concurrent requests into batches, pads
  each batch up a small ladder of bucketed shapes (the ladder
  ``analysis.lint.suggest_buckets`` derives from the shapes L001
  inspects), and runs them through ``Predictor.run_async`` clones. A
  warm process over one ``FLAGS_exec_cache_dir`` serves ANY mix of
  request shapes with **zero fresh compiles**, and padding rows are
  sliced away so batched results are bit-identical to per-request
  ``Predictor.run``. Admission control (bounded queue depth,
  per-request deadlines) rejects overload with typed errors instead of
  wedging; latency / queue-depth / batch-occupancy metrics land in the
  process metrics registry.
* ``generation.SlotDecodeSession`` — continuous batching for
  generation: the KV-cached decoder's caches become a slot-paged pool
  (``models.transformer.build_slot_decoder``) where each in-flight
  sequence owns one slot row, admissions scatter a new sequence's
  encoder state into a free slot mid-flight, and ONE fixed-shape step
  executable advances every active sequence per token — the
  ragged-paged-attention serving shape, sized to this repo.
* ``loadgen`` — the deterministic demo model and request stream the
  serving tests share (``tests/test_serving.py`` and three more);
  timings come from ``perfbench/``, which has a load generator of its
  own.
* ``snapshot.DecodeSnapshotManager`` — preemption-safe decode:
  atomic, digest-verified snapshot/restore of a live
  ``SlotDecodeSession`` (live KV pages gathered through the page
  table, allocator/prefix-trie/pending-queue state, SIGTERM ->
  finish dispatch -> final snapshot -> die by the signal); a restored
  process's tokens are bit-identical to the uninterrupted run's.
* ``degradation.HealthMonitor`` — the healthy -> brownout -> shed
  state machine both the server (queue depth) and the decode session
  (page occupancy) shed load through; refusals are typed retriable
  ``DegradedError``\\ s with retry-after hints, never wedged callers.
* ``frontend.ServingFrontend`` / ``client.ServingClient`` — the
  NETWORK serving plane: the whole stack above behind a socket on the
  shared JSON-lines substrate — unary ``predict`` with wire deadlines
  mapped to the typed admission errors, STREAMING ``generate`` (token
  chunks flushed per decode dispatch; ``admit_group`` best-of-N and
  prefix reuse work remotely), ``metrics``/``health`` endpoints,
  disconnect-safe reclamation (a killed client's slot and KV pages
  return to the pool), and a client that re-raises the same typed
  errors with classified retry + reconnect across frontend restarts.
* ``router.ServingRouter`` / ``router.RouterMember`` — the FLEET tier:
  N frontends register with heartbeat leases behind one router
  address; unary requests round-robin, streaming admissions ride
  prefix-affinity consistent hashing (``prefix_hit_rate`` survives
  scale-out), degraded members shed new admissions to healthy peers,
  and live sessions MIGRATE between frontends — planned drain and
  lease-lapse failover both restore a serialized decode snapshot on a
  survivor and re-drive every client stream from exactly the last
  delivered (rid, seq) chunk: bit-identical tokens, zero lost or
  duplicated.

``docs/SERVING.md`` ("Batching server" / "Network front end") is the
operator's guide.
"""

from paddle_tpu.serving import client  # noqa: F401
from paddle_tpu.serving import degradation  # noqa: F401
from paddle_tpu.serving import frontend  # noqa: F401
from paddle_tpu.serving import generation  # noqa: F401
from paddle_tpu.serving import kv_pool  # noqa: F401
from paddle_tpu.serving import loadgen  # noqa: F401
from paddle_tpu.serving import server  # noqa: F401
from paddle_tpu.serving import snapshot  # noqa: F401
from paddle_tpu.serving.client import (  # noqa: F401
    ServingClient,
    StreamBrokenError,
)
from paddle_tpu.serving.degradation import (  # noqa: F401
    DegradedError,
    HealthMonitor,
)
from paddle_tpu.serving.generation import (  # noqa: F401
    NoFreeGroupError,
    NoFreePageError,
    NoFreeSlotError,
    Sampler,
    SlotDecodeSession,
)
from paddle_tpu.serving.kv_pool import (  # noqa: F401
    PagePool,
    PrefixCache,
)
from paddle_tpu.serving.server import (  # noqa: F401
    BatchingServer,
    DeadlineExceededError,
    QueueFullError,
    ServerClosedError,
    ServingError,
    ServingFuture,
    WaitTimeoutError,
)
from paddle_tpu.serving.frontend import ServingFrontend  # noqa: F401
from paddle_tpu.serving import router  # noqa: F401
from paddle_tpu.serving.router import (  # noqa: F401
    ConsistentRing,
    RouterMember,
    ServingRouter,
)
from paddle_tpu.serving.snapshot import (  # noqa: F401
    DecodeSnapshotManager,
    SnapshotMismatchError,
)
