"""Device pool: coalesced groups multiplexed over an explicit device set.

PR 7's router executed every merged engine call inline on the drain
thread against the process-global default device. The pool gives the
serve tier an explicit device topology instead: one worker thread per
device, each pinned via ``jax.default_device`` (thread-local in jax), so

  * groups drained from one window run **concurrently across devices**
    (one DP dispatch per device at a time), and
  * the host-side work of a group — merging trimmed queries before the
    call, slicing the batched result back per client and resolving
    futures after it — runs on the worker threads, overlapping the next
    group's device DP instead of serializing behind it on the drain
    thread.

Device selection (``devices=``):

  * ``None``  — one worker on the process-default device (PR 7
    behavior, still the default);
  * ``'all'`` — one worker pinned to each ``jax.local_devices()`` entry;
  * ``int n`` — the first n local devices;
  * an explicit sequence of jax devices (duplicates allowed: two
    workers sharing one device still overlap host slicing with DP).

Routing is **executable-affine** (``pick_device``): jit executables are
compiled per device assignment, so a group's first landing on a device
pays an XLA compile for its bucket shape (``batcher.group_shape``).
Naive least-loaded routing recompiles that shape on every device a
transient backlog happens to spill onto — a recurring multi-second tail
at serving time. Instead a process-global warm map (mirroring the jit
cache, which is process-global too — a new pool inherits placements
already compiled) remembers which devices have run each shape, and the
pool prefers the least-loaded *warm* one; it grows the warm set
onto a cold idle device only when every warm device is busy (sustained
same-shape pressure makes the one-off compile an investment, after
which the shape is warm there too) and only one cold landing at a time
per shape — an unthrottled grow rule avalanches, because the compile
itself keeps the cold device busy and pushes the next group onto yet
another cold device. A never-seen shape goes to the globally
least-loaded device.

Correctness: a group runs start-to-finish on one worker, the engine's
executables are compiled per device assignment, and the DP is integer
(int32) — so pooled answers are bitwise identical to a single-device
drain (pinned by ``tests/test_serve.py`` and the ``serve_bench``
``served_vs_offline`` gate). Each worker owns a private work queue;
the pool is unbounded because admission is already bounded upstream by
the ``AdmissionQueue``.
"""
from __future__ import annotations

import collections
import queue as _stdqueue
import threading

from jax.profiler import TraceAnnotation

from . import batcher
from .telemetry import request_ids

__all__ = ["DevicePool", "clear_affinity_cache", "pick_device"]

# The jit cache is process-global (keyed on device assignment), so the
# warm map must be too: a fresh pool over the same devices inherits
# every placement already compiled instead of re-discovering them —
# bounded LRU like the distributed pipeline cache.
AFFINITY_CACHE_MAX = 1024
_affinity_lock = threading.Lock()
_warm_devices: "collections.OrderedDict" = collections.OrderedDict()
_growing: set = set()          # shapes with a cold landing in flight


def clear_affinity_cache():
    """Drop the process-global shape→devices warm map (tests)."""
    with _affinity_lock:
        _warm_devices.clear()
        _growing.clear()


def _mark_warm(shape, device):
    with _affinity_lock:
        _warm_devices.setdefault(shape, set()).add(device)
        _warm_devices.move_to_end(shape)
        while len(_warm_devices) > AFFINITY_CACHE_MAX:
            _warm_devices.popitem(last=False)


def resolve_devices(devices):
    """Normalize the ``devices=`` config into a list of worker bindings
    (``None`` = process-default device, i.e. no pinning)."""
    if devices is None:
        return [None]
    import jax
    if devices == "all":
        return list(jax.local_devices())
    if isinstance(devices, int):
        local = jax.local_devices()
        if not 1 <= devices <= len(local):
            raise ValueError(
                f"devices={devices} but only {len(local)} local "
                f"device(s) are visible; pass 1..{len(local)}, 'all', "
                "or an explicit device sequence")
        return local[:devices]
    out = list(devices)
    if not out:
        raise ValueError("devices= must name at least one device "
                         "(or None for the process default)")
    return out


# A warm device must have this many groups in flight/queued before the
# pool pays a cold compile to spread the shape: load 1 is every burst's
# steady state (one group per window), load >= 2 is a real backlog.
GROW_LOAD = 2


def pick_device(loads, warm, growing=False):
    """Executable-affinity routing policy (pure; caller holds the lock).

    ``loads`` is the per-device in-flight group count; ``warm`` the set
    of device indices that have already compiled this group's shape;
    ``growing`` is True while a previous cold landing of this shape is
    still in flight (i.e. the shape is mid-compile somewhere).

      * never-seen shape            → globally least-loaded device;
      * least-loaded warm device is
        below ``GROW_LOAD``         → that device (free cache reuse);
      * warm backlogged, cold idle,
        and not already growing     → lowest cold idle index (grow the
                                      warm set under pressure — pay one
                                      compile to add parallelism);
      * otherwise                   → least-loaded warm device (queueing
                                      milliseconds beats compiling
                                      seconds).

    The ``growing`` gate caps cold landings at one in flight per shape,
    and ``GROW_LOAD`` demands a real backlog first. Without them a
    compile *avalanches*: the first cold landing keeps its device busy
    for seconds, so every subsequent same-shape group "grows" onto yet
    another cold device and recompiles there — the pool floods itself
    with concurrent compiles of one executable.

    Ties break on the lowest index for determinism."""
    if warm:
        w = min(warm, key=lambda i: (loads[i], i))
        if loads[w] < GROW_LOAD or growing:
            return w
        for i, load in enumerate(loads):
            if load == 0 and i not in warm:
                return i
        return w
    return min(range(len(loads)), key=lambda i: (loads[i], i))


class DevicePool:
    """Per-device worker threads executing coalesced request groups."""

    def __init__(self, devices=None, *, name: str = "repro-serve-dev"):
        self._devices = resolve_devices(devices)
        self._lock = threading.Lock()
        self._idle = threading.Condition(self._lock)
        self._inflight = 0           # groups submitted, not yet finished
        self._loads = [0] * len(self._devices)
        self._queues = [_stdqueue.SimpleQueue() for _ in self._devices]
        self._closed = False
        self._threads = []
        for i, dev in enumerate(self._devices):
            t = threading.Thread(target=self._worker, args=(i, dev),
                                 name=f"{name}{i}", daemon=True)
            t.start()
            self._threads.append(t)

    @property
    def devices(self) -> list:
        return list(self._devices)

    @property
    def size(self) -> int:
        return len(self._devices)

    # ------------------------------------------------------------------
    # submission
    # ------------------------------------------------------------------

    def submit(self, group, telemetry=None):
        """Route one coalesced group to a worker (executable-affine, see
        ``pick_device``). Every member future is guaranteed an answer
        (``execute_group``'s contract); returns immediately."""
        with self._lock:
            if self._closed:
                raise RuntimeError("device pool is closed")
            shape = batcher.group_shape(group)
            with _affinity_lock:
                warm_devs = _warm_devices.setdefault(shape, set())
                _warm_devices.move_to_end(shape)
                while len(_warm_devices) > AFFINITY_CACHE_MAX:
                    _warm_devices.popitem(last=False)
                warm = {i for i, d in enumerate(self._devices)
                        if d in warm_devs}
                i = pick_device(self._loads, warm,
                                growing=shape in _growing)
                cold = i not in warm
                if cold:
                    _growing.add(shape)
                warm_devs.add(self._devices[i])
            self._loads[i] += 1
            self._inflight += 1
        self._queues[i].put((group, telemetry, shape if cold else None))

    def warmup(self, request) -> int:
        """Compile ``request``'s executables on every pool device and
        prime the affinity map, so no client ever pays the shape's XLA
        compile or waits out the warm set's backlog-gated growth.

        Runs sequentially (concurrent cold compiles contend with each
        other) and blocks until done — call before accepting traffic,
        with requests shaped like the coalesced buckets production
        windows will form (e.g. ``window_full_queries`` queries at
        serving length). Returns the number of devices warmed."""
        with self._lock:
            if self._closed:
                raise RuntimeError("device pool is closed")
        p = batcher.Pending(request=request, future=None, trace=None)
        shape = batcher.group_shape([p])
        for dev in self._devices:
            if dev is None:
                request.run()
            else:
                import jax
                with jax.default_device(dev):
                    request.run()
            _mark_warm(shape, dev)
        return len(self._devices)

    def join(self):
        """Block until every submitted group has finished executing."""
        with self._idle:
            self._idle.wait_for(lambda: self._inflight == 0)

    def close(self, *, wait: bool = True):
        """Stop the workers (after finishing queued work when ``wait``)."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        for q in self._queues:
            q.put(None)
        if wait:
            for t in self._threads:
                t.join(timeout=10.0)

    # ------------------------------------------------------------------
    # workers
    # ------------------------------------------------------------------

    def _worker(self, i: int, dev):
        while True:
            task = self._queues[i].get()
            if task is None:
                return
            group, telemetry, cold_shape = task
            members = list(batcher.group_members(group))
            try:
                with TraceAnnotation(
                        "serve.execute",
                        req=request_ids(p.trace for p in members),
                        requests=len(members),
                        queries=sum(len(p.entries) for p in group)):
                    if dev is None:
                        batcher.execute_group(group, telemetry=telemetry)
                    else:
                        import jax
                        with jax.default_device(dev):
                            batcher.execute_group(group,
                                                  telemetry=telemetry)
            except Exception as exc:                     # noqa: BLE001
                # execute_group never raises by contract; this is a
                # last-ditch guard so a pool bug can never orphan
                # admitted futures.
                batcher.fail_group(group, exc, telemetry=telemetry)
            finally:
                if cold_shape is not None:
                    with _affinity_lock:
                        _growing.discard(cold_shape)
                with self._idle:
                    self._loads[i] -= 1
                    self._inflight -= 1
                    if self._inflight == 0:
                        self._idle.notify_all()

    def __enter__(self) -> "DevicePool":
        return self

    def __exit__(self, *exc):
        self.close()
