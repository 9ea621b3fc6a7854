"""`BlasxContext` — the persistent handle layer of the two-layer BLAS API.

The paper's central claim is that a locality-aware runtime with a
two-level tile cache (ALRU L1 per device + MESI-X L2 across peers)
makes communication cost trivial.  That only holds if the caches
*survive* between calls: a context owns one long-lived
:class:`~repro.core.runtime.BlasxRuntime` and keeps its tile caches
warm across routines, so chained workloads (Cholesky-style
``syrk -> trsm -> gemm`` sweeps, LM serving layers calling ``gemm``
per projection) stop re-paying H2D traffic on every call.

Key objects
-----------
``BlasxContext``
    cuBLAS-handle-style lifetime object.  All six L3 routines are
    methods (``ctx.gemm`` ... ``ctx.trsm``); each returns a
    :class:`MatrixHandle` that can be fed straight into the next call
    without re-tiling.  Per-call ledger snapshots live in
    ``ctx.calls``; cumulative counters in ``ctx.stats()``.
``MatrixHandle``
    A host matrix bound to a context under a globally unique
    ``matrix_id``.  Tile keys derive from that id, so a handle's tiles
    hit the warm ALRU/MESI-X caches on every subsequent call.  Handles
    from different contexts never alias.
``default_context()``
    Module-cached context used by the legacy ``repro.core.blas3``
    wrappers and the ``repro.api.cblas`` layer.

Example
-------
>>> from repro.api import BlasxContext
>>> with BlasxContext() as ctx:
...     W = ctx.tile(weights)          # device-resident handle
...     for x in batches:
...         y = ctx.gemm(ctx.tile(x), W)   # W's tiles stay cached
...         use(y.array())
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import json
import threading
from typing import Dict, List, Optional, Sequence, Union

import numpy as np

from .. import telemetry
from ..core import task as taskmod
from ..core.dtypes import (SUPPORTED_DTYPES, canonical_dtype,
                           promote_dtypes, validate_backend_dtype)
from ..core.runtime import BlasxRuntime, RuntimeConfig
from ..core.tiling import TiledMatrix
from .futures import BlasFuture, SerialExecutor

DEFAULT_TILE = 256

# ctx.calls keeps at most this many CallRecords (cumulative counters in
# stats() are unaffected) so a long-lived default context stays bounded
MAX_CALL_RECORDS = 512

ArrayLike = Union[np.ndarray, "MatrixHandle"]

# one global id stream so handles never alias across contexts either
_MATRIX_IDS = itertools.count()


def _api_call(method):
    """Runs a public routine inside a ``blasx.call`` span (its self time:
    argument checks and the ``CallRecord`` snapshot)."""
    routine = method.__name__

    @functools.wraps(method)
    def call(self, *args, **kwargs):
        with telemetry.span("blasx.call", routine=routine):
            return method(self, *args, **kwargs)

    return call


def _as2d(x, name: str, dtype=None) -> np.ndarray:
    a = np.asarray(x) if dtype is None else np.asarray(x, dtype=dtype)
    if a.ndim != 2:
        raise ValueError(f"{name} must be 2-D, got shape {a.shape}")
    return a


class MatrixHandle:
    """A tiled matrix registered with one :class:`BlasxContext`.

    The handle pins a globally unique ``matrix_id`` so that tile keys
    are stable across calls — the warm-cache contract.  The underlying
    data stays host-resident (the paper's out-of-core model); device
    copies of individual tiles live in the runtime's ALRU caches.

    Mutating ``handle.array()`` in place after tiles have been cached
    makes device copies stale; call :meth:`invalidate` afterwards.
    """

    def __init__(self, ctx: "BlasxContext", tiled: TiledMatrix):
        self._ctx = ctx
        self._tiled = tiled

    @property
    def matrix_id(self) -> str:
        return self._tiled.matrix_id

    @property
    def shape(self):
        return self._tiled.data.shape

    @property
    def tile(self) -> int:
        return self._tiled.grid.tile

    @property
    def dtype(self) -> np.dtype:
        """Storage precision of the handle (and of its cached tiles)."""
        return self._tiled.data.dtype

    @property
    def tiled(self) -> TiledMatrix:
        return self._tiled

    def array(self) -> np.ndarray:
        """The host-resident data (no copy)."""
        return self._tiled.data

    def invalidate(self) -> int:
        """Drop every cached device copy of this matrix's tiles.

        Needed after in-place mutation of :meth:`array`.  Returns the
        number of tiles dropped."""
        return self._ctx._invalidate_matrix(self.matrix_id)

    def __repr__(self) -> str:
        return (f"MatrixHandle({self.matrix_id}, shape={self.shape}, "
                f"tile={self.tile})")


@dataclasses.dataclass(frozen=True)
class CallRecord:
    """Ledger snapshot of one routine executed by a context (deltas
    against the runtime's cumulative counters).

    The bytes and ``makespan`` are the runtime's model, not measurements:
    ``h2d_bytes`` counts the tile-cache misses the model charges (on a
    TPU every step group stages all of its tiles anew; the bytes handed
    to the device are the ``h2d_bytes`` counter of ``repro.telemetry``),
    and ``makespan`` is the sim engine's clock, from K40c/PCIe constants.
    """

    index: int
    routine: str
    h2d_bytes: int
    d2h_bytes: int
    d2d_bytes: int
    tasks: int
    steals: int
    l1_hits: int
    l1_misses: int
    makespan: float        # modeled seconds this call added (sim mode)

    @property
    def input_bytes(self) -> int:
        return self.h2d_bytes + self.d2d_bytes


class BlasxContext:
    """Persistent two-level-cache BLAS handle (cuBLAS-handle analogue).

    Parameters
    ----------
    config:
        Any :class:`~repro.core.runtime.RuntimeConfig`; defaults to a
        single simulated device.  Ignored when ``runtime`` is given.
    runtime:
        Adopt an existing :class:`BlasxRuntime` instead of building
        one (used by the legacy wrappers' ``runtime=`` passthrough).
    tile:
        Default tile size for :meth:`tile` and auto-tiled numpy inputs.
    backend:
        Execution backend shorthand (``"numpy" | "jax" | "pallas"``);
        overrides ``config.backend``.  With ``runtime=`` it must match
        the adopted runtime's backend (a runtime's backend is fixed at
        construction).
    dtype:
        Default storage/compute precision for the context.  When set,
        :meth:`tile` and the routines cast raw-array operands to it
        and outputs are produced in it; tile byte sizes (ALRU/heap
        capacity, MESI-X transfer ledger, comm model) follow the
        storage dtype.  ``float64``/``float32`` run on every backend;
        ``float16``/``bfloat16`` need the jax or pallas backend (the
        engines accumulate them in float32).  ``None`` (default)
        preserves the legacy promote-from-inputs behaviour.  Each
        routine also takes a per-call ``dtype=`` that overrides this.
    auto_tune:
        Enable the shape-adaptive runtime autotuner
        (``repro.tuning``).  Raw-array calls without an explicit
        ``tile=`` then resolve their tile size per (routine, shape
        bucket, dtype) from the tuning cache — resolving cache misses
        per the tuner *mode* — and, while the context is still cold
        (no call has executed), the first tuned call may rebuild the
        runtime with the tuned ``n_streams``/``policy``.  Accepts a
        bool or a mode string: ``True`` / ``"sweep"`` sweeps every
        candidate ``(tile, n_streams, policy)`` through metadata-only
        shadow runs; ``"model"`` predicts makespans with the learned
        cost model (``repro.tuning.model``) and confirms the predicted
        winner in a single shadow run; ``"auto"`` uses the model only
        once it is trained and its uncertainty is tight, sweeping
        otherwise (see ``docs/TUNING.md``).  Calls on
        :class:`MatrixHandle` operands keep the handle's tile
        (re-tiling would break the warm-cache contract).  Any call may
        also pass ``tile="auto"`` explicitly — with or without
        ``auto_tune`` — to resolve just the tile size.
    tuning_cache:
        Where tuned configs persist: ``None`` (default) shares the
        process-wide cache (second context with the same topology is a
        pure cache hit), a path string gives a JSON file that also
        survives processes, or pass a ``repro.tuning.TuningCache``.

    The context is a context manager; :meth:`close` shuts down the
    async executor and drops all cached tiles.  All methods are
    thread-safe: calls serialize on one internal lock (the runtime is
    not re-entrant), which is also what makes :meth:`submit` futures
    well-ordered.
    """

    # lock-discipline declarations (repro.analysis, docs/ANALYSIS.md):
    # _lock is reentrant, so the routine wrappers may take it around
    # the lock-held helpers.  runtime/cfg/tile_size/dtype/_auto_tune/
    # _tune_mode/_tuning_cache/_owns_runtime are fixed after __init__
    # and stay unlisted.
    _GUARDED_BY = {"_lock": (
        "_closed", "_executor", "calls", "n_calls", "_tenant",
        "_boost", "_tuner")}
    _LOCK_HELD = ("_run", "_get_tuner", "_maybe_adopt_schedule")

    def __init__(self, config: Optional[RuntimeConfig] = None, *,
                 runtime: Optional[BlasxRuntime] = None,
                 tile: int = DEFAULT_TILE,
                 backend: Optional[str] = None,
                 dtype=None,
                 auto_tune: Union[bool, str] = False,
                 tuning_cache=None):
        if backend is not None:
            if runtime is not None:
                if runtime.cfg.backend != backend:
                    raise ValueError(
                        f"backend={backend!r} conflicts with adopted "
                        f"runtime's backend {runtime.cfg.backend!r}")
            elif config is None:
                config = RuntimeConfig(n_devices=1, mode="sim",
                                       backend=backend)
            elif config.backend != backend:
                config = dataclasses.replace(config, backend=backend)
        self._owns_runtime = runtime is None
        self.runtime = runtime if runtime is not None else BlasxRuntime(
            config or RuntimeConfig(n_devices=1, mode="sim"))
        self.cfg = self.runtime.cfg
        self.tile_size = tile
        # fail fast: an unsupported (dtype, backend) pair is a config
        # error, not something to surface on the first routine call
        self.dtype = (validate_backend_dtype(dtype, self.cfg.backend)
                      if dtype is not None else None)
        self.calls: List[CallRecord] = []   # last MAX_CALL_RECORDS only
        self.n_calls = 0                    # lifetime count
        self._lock = threading.RLock()
        self._executor: Optional[SerialExecutor] = None
        self._closed = False
        # auto_tune accepts a bool (True == "sweep", the pre-model
        # behaviour) or a mode string; the mode also applies to
        # explicit tile="auto" calls on an auto_tune=False context
        if isinstance(auto_tune, str):
            from ..tuning import MODES
            if auto_tune not in MODES:
                raise ValueError(f"auto_tune must be a bool or one of "
                                 f"{MODES}, got {auto_tune!r}")
            self._auto_tune = True
            self._tune_mode = auto_tune
        else:
            self._auto_tune = bool(auto_tune)
            self._tune_mode = "sweep"
        self._tuning_cache = tuning_cache
        self._tuner = None                  # built lazily (repro.tuning)
        # serving attribution (repro.serve): tenant tag + priority-class
        # boost the next _run threads into the runtime; set via
        # request_scope so they cover exactly one request
        self._tenant: Optional[str] = None
        self._boost: float = 0.0

    # ------------------------------------------------------------ lifecycle
    def __enter__(self) -> "BlasxContext":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self) -> None:
        """Shut down the async executor and drop all cached tiles.
        Idempotent; further routine calls raise ``RuntimeError``.

        The executor is drained *outside* the context lock: in-flight
        workers take that lock to run routines, so holding it through
        ``shutdown(wait=True)`` would deadlock the closing thread."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            executor, self._executor = self._executor, None
        if executor is not None:
            executor.shutdown()
        with self._lock:
            # an adopted runtime (runtime= in the constructor) belongs
            # to the caller — leave its caches and ledgers alone
            if self._owns_runtime:
                self.runtime.reset()

    @property
    def closed(self) -> bool:
        # _closed flips under _lock in close(); an unlocked read races
        # with a closing thread (LD001).  The RLock makes this safe to
        # take even from code already holding it.
        with self._lock:
            return self._closed

    def _check_open(self) -> None:
        with self._lock:
            closed = self._closed
        if closed:
            raise RuntimeError("BlasxContext is closed")

    def _resolve_dtype(self, dtype) -> Optional[np.dtype]:
        """Per-call ``dtype=`` beats the context default; ``None`` when
        neither is set (legacy promote-from-inputs).  Validated against
        the execution backend (half precisions are jax/pallas-only)."""
        if dtype is None:
            return self.dtype
        return validate_backend_dtype(dtype, self.cfg.backend)

    # ------------------------------------------------------------- handles
    def tile(self, data, tile: Optional[int] = None,
             dtype=None) -> MatrixHandle:
        """Register a host matrix and return its device-resident handle.

        Tiles fetched during later calls stay in the runtime's L1/L2
        caches keyed by this handle's unique ``matrix_id`` — reusing
        the handle is what turns repeat traffic into cache hits.

        ``dtype`` (or the context default) casts the data on
        registration; the handle then stores — and its tiles are
        cached/transferred at — that precision.  Validated against the
        backend up front: registering tiles at a precision the engine
        can never execute is a config error.  Re-registering an
        existing handle only enforces a dtype that was passed
        explicitly — a handle deliberately tiled at a non-default
        precision stays adoptable under the context default."""
        self._check_open()
        if isinstance(tile, str):
            # a handle has no routine context to tune against; callers
            # wanting tuned handles pre-resolve via auto_tile
            raise ValueError(
                "tile='auto' is resolved per routine call; use "
                "ctx.auto_tile(routine, m, k, n) to pre-resolve a tuned "
                "tile for ctx.tile()")
        dt = self._resolve_dtype(dtype)
        if isinstance(data, MatrixHandle):
            return self._adopt(data, dt if dtype is not None else None,
                               "matrix")
        a = _as2d(data, "matrix", dt)
        mid = f"M{next(_MATRIX_IDS)}"
        return MatrixHandle(self, TiledMatrix(mid, a, tile or self.tile_size))

    def _adopt(self, h: MatrixHandle, dtype=None,
               name: str = "matrix") -> MatrixHandle:
        if h._ctx is not self:
            raise ValueError(
                f"handle {h.matrix_id} belongs to a different context; "
                "tile caches do not transfer between contexts")
        if dtype is not None and h.array().dtype != dtype:
            # a handle owns its storage; recasting behind the caller's
            # back would silently decouple it from its cached tiles
            raise ValueError(
                f"{name}: handle {h.matrix_id} is {h.array().dtype}, "
                f"call requested dtype {np.dtype(dtype).name}; re-tile "
                "the data at the desired precision")
        return h

    def _coerce(self, x: ArrayLike, name: str, tile: Optional[int],
                ephemeral: List["MatrixHandle"],
                dtype: Optional[np.dtype] = None,
                strict: bool = False) -> MatrixHandle:
        """Handle passthrough; raw arrays are tiled fresh (cold) and
        recorded in ``ephemeral`` — their matrix id is unique to this
        one call, so any tiles they leave in the caches could never be
        hit again and are dropped right after the run (keeps legacy
        per-call traffic from squatting on cache capacity).  ``dtype``
        casts raw arrays; handles must already match it only when
        ``strict`` (an explicit per-call ``dtype=``) — a handle tiled
        at a non-default precision stays usable under the context
        default (its tiles are cached at its own dtype; only the
        output follows the default)."""
        if isinstance(x, MatrixHandle):
            if tile is not None and x.tile != tile:
                raise ValueError(
                    f"{name}: handle tile {x.tile} != requested tile {tile}")
            return self._adopt(x, dtype if strict else None, name)
        with telemetry.span("blasx.prep"):
            a = _as2d(x, name, dtype)
            # pass the resolved dtype through: tile() would otherwise
            # re-resolve against the context default and recast a
            # per-call dtype= override (None stays None -> tile applies
            # the default)
            h = self.tile(a, tile or self.tile_size, dtype=dtype)
        ephemeral.append(h)
        return h

    def _fresh_out(self, rows: int, cols: int, tile: int, dtype,
                   seed: Optional[np.ndarray] = None) -> MatrixHandle:
        """New output matrix under a fresh id (seeded from C or zeros)."""
        with telemetry.span("blasx.prep"):
            if seed is not None:
                data = np.array(seed, dtype=dtype, copy=True)
            else:
                data = np.zeros((rows, cols), dtype=dtype)
        mid = f"M{next(_MATRIX_IDS)}"
        return MatrixHandle(self, TiledMatrix(mid, data, tile))

    def _invalidate_matrix(self, matrix_id: str) -> int:
        with self._lock:
            n = 0
            for dev in self.runtime.devices:
                for key in dev.alru.keys():
                    if key.matrix_id == matrix_id:
                        self.runtime.directory.on_evict(key, dev.id)
                        dev.alru.invalidate(key)
                        dev.store.pop(key, None)
                        n += 1
            return n

    # ------------------------------------------------------------ plumbing
    def _run(self, routine: str, tasks, mats: Dict[str, TiledMatrix],
             out_id: str,
             ephemeral: Optional[List[MatrixHandle]] = None) -> CallRecord:
        """Execute one taskized routine and append a ledger snapshot."""
        rt = self.runtime
        before_comm = rt.total_comm_bytes()
        before = [(d.ledger.tasks, d.ledger.steals, d.alru.hits,
                   d.alru.misses) for d in rt.devices]
        t0 = rt.makespan()
        rt.run(tasks, mats, out_id,
               tenant=self._tenant, priority_boost=self._boost)
        after_comm = rt.total_comm_bytes()
        d_tasks = sum(d.ledger.tasks for d in rt.devices) - \
            sum(b[0] for b in before)
        d_steals = sum(d.ledger.steals for d in rt.devices) - \
            sum(b[1] for b in before)
        d_hits = sum(d.alru.hits for d in rt.devices) - \
            sum(b[2] for b in before)
        d_miss = sum(d.alru.misses for d in rt.devices) - \
            sum(b[3] for b in before)
        with telemetry.span("blasx.prep"):
            for h in ephemeral or ():
                self._invalidate_matrix(h.matrix_id)
        rec = CallRecord(
            index=self.n_calls, routine=routine,
            h2d_bytes=after_comm["h2d"] - before_comm["h2d"],
            d2h_bytes=after_comm["d2h"] - before_comm["d2h"],
            d2d_bytes=after_comm["d2d"] - before_comm["d2d"],
            tasks=d_tasks, steals=d_steals,
            l1_hits=d_hits, l1_misses=d_miss,
            makespan=rt.makespan() - t0,
        )
        self.n_calls += 1
        self.calls.append(rec)
        if len(self.calls) > MAX_CALL_RECORDS:
            del self.calls[0]
        return rec

    @property
    def last_call(self) -> Optional[CallRecord]:
        # calls is mutated under _lock by _run; lock the read too
        with self._lock:
            return self.calls[-1] if self.calls else None

    # ------------------------------------------------------------- serving
    @contextlib.contextmanager
    def request_scope(self, tenant: Optional[str] = None,
                      priority_boost: float = 0.0):
        """Attribute every routine executed inside the ``with`` body to
        ``tenant`` (tagging its cached tiles for the per-tenant ALRU
        quotas) and add ``priority_boost`` to each task's Eq. 3
        locality priority.  Holds the context lock for the duration —
        routine execution takes the same (reentrant) lock, so scopes
        from concurrent threads serialize rather than interleave their
        attribution.  This is the channel ``repro.serve`` uses per
        request."""
        self._check_open()
        with self._lock:
            prev = (self._tenant, self._boost)
            self._tenant = tenant
            self._boost = float(priority_boost)
            try:
                yield self
            finally:
                self._tenant, self._boost = prev

    def set_tenant_quota(self, tenant: str,
                         nbytes: Optional[int]) -> None:
        """Cap ``tenant``'s resident tile-cache bytes on every device
        (None removes the cap); see
        :meth:`repro.core.runtime.BlasxRuntime.set_tenant_quota`."""
        self._check_open()
        with self._lock:
            self.runtime.set_tenant_quota(tenant, nbytes)

    # --------------------------------------------------------------- stats
    def stats(self) -> Dict[str, object]:
        """Cumulative session counters: total comm bytes, per-device
        ledgers, call count, modeled makespan."""
        rt = self.runtime
        with self._lock:
            n_calls = self.n_calls
        return {
            "calls": n_calls,
            "backend": rt.cfg.backend,
            "comm_bytes": rt.total_comm_bytes(),
            "makespan": rt.makespan(),
            "launch": rt.launch_stats(),
            "devices": rt.stats(),
        }

    def trace(self, path: Optional[str] = None) -> dict:
        """Chrome-trace JSON of every sim batch this context scheduled.

        Open the written file in ``chrome://tracing`` or
        https://ui.perfetto.dev: one track group per device, one track
        per stream and per H2D/D2D/D2H link lane, so stream overlap
        and host-link contention are visible span by span.  The trace
        accumulates across calls; :meth:`reset` starts a fresh one.
        With ``path`` the JSON is also written to disk.  Outside the
        sim event engine (``mode="threads"`` /
        ``time_model="lump"``) the trace is valid but has no spans."""
        self._check_open()
        with self._lock:
            tr = self.runtime.trace()
        if path is not None:
            with open(path, "w") as f:
                json.dump(tr, f)
        return tr

    def reset_stats(self) -> None:
        """Zero every ledger/counter *without* dropping cached tiles —
        session-boundary accounting for long-lived contexts."""
        with self._lock:
            self.runtime.reset_stats()
            self.calls.clear()
            self.n_calls = 0

    def reset(self) -> None:
        """Drop all cached tiles AND zero all counters (cold restart)."""
        with self._lock:
            self.runtime.reset()
            self.calls.clear()
            self.n_calls = 0

    # ---------------------------------------------------------------- async
    def submit(self, routine, *args, **kwargs) -> BlasFuture:
        """Submit an L3 call for asynchronous execution.

        ``routine`` is a routine name (``"gemm"`` ... ``"trsm"``,
        ``"gemm_batched"``) or any callable.  Returns a
        :class:`BlasFuture`; the result is whatever the synchronous
        method returns (a :class:`MatrixHandle` for the six routines).
        Submissions execute in order on a background thread, so
        independent calls overlap with the caller and chained calls
        may safely pass not-yet-materialized handles obtained from
        ``future.result()``."""
        if isinstance(routine, str):
            fn = getattr(self, routine, None)
            if fn is None or not callable(fn):
                raise ValueError(f"unknown routine {routine!r}")
        else:
            fn = routine
        # closed-check, lazy creation and enqueue all under the lock so a
        # concurrent close() can neither leak a fresh executor nor null
        # the one we are about to use
        with self._lock:
            self._check_open()
            if self._executor is None:
                self._executor = SerialExecutor(name="blasx-ctx")
            return self._executor.submit(fn, *args, **kwargs)

    # ==================================================== runtime autotuning
    def _get_tuner(self):
        """Lazily build the :class:`repro.tuning.Autotuner` bound to
        this context's topology (imported here: tuning depends on
        core.runtime, the api layer must not import it eagerly)."""
        if self._tuner is None:
            from ..tuning import Autotuner
            self._tuner = Autotuner(self.cfg, cache=self._tuning_cache,
                                    mode=self._tune_mode,
                                    default_tile=self.tile_size)
        return self._tuner

    def auto_tile(self, routine: str, m: int, k: Optional[int] = None,
                  n: Optional[int] = None, dtype=None) -> int:
        """Resolve the tuned tile size for one (routine, shape, dtype).

        Consults the tuning cache (topology fingerprint + routine +
        shape bucket + dtype); on a miss, sweeps candidate
        ``(tile, n_streams, policy)`` configs through metadata-only
        shadow runs on the virtual clock and caches the winner.  With
        ``auto_tune=True`` and a still-cold context the tuned
        scheduling knobs are also adopted (see :meth:`tuning_report`).
        This is what ``tile="auto"`` calls under the hood; batched
        entry points use it to resolve one tile for a whole batch."""
        self._check_open()
        with self._lock:
            dt = self._resolve_dtype(dtype)
            best = self._get_tuner().tune(
                routine, m, k, n, dtype=dt if dt is not None else np.float64)
            self._maybe_adopt_schedule(best)
            return best.tile

    def _maybe_adopt_schedule(self, best) -> None:
        """Adopt the tuned ``(n_streams, policy)`` by rebuilding the
        runtime — only with ``auto_tune=True``, only on a context that
        owns its runtime, and only while it is still cold (nothing
        executed, so no warm cache or ledger is lost).  The first
        tuned call pins the schedule; later calls tune tiles only."""
        if not self._auto_tune or not self._owns_runtime:
            return
        if self.runtime.runs > 0 or self.n_calls > 0:
            return
        wc = bool(getattr(best, "work_centric", False))
        if (best.n_streams == self.cfg.n_streams
                and best.policy == self.cfg.policy
                and wc == self.cfg.work_centric):
            return
        cfg = dataclasses.replace(self.cfg, n_streams=best.n_streams,
                                  rs_slots=None, policy=best.policy,
                                  work_centric=wc)
        self.runtime = BlasxRuntime(cfg)
        self.cfg = cfg

    def _tile_arg(self, tile, routine: str, m: int, k: int, n: int,
                  dtype, operands) -> Optional[int]:
        """Resolve a routine's ``tile=`` argument, which may be an int
        (as ever), ``"auto"`` (tune this call), or ``None`` — which
        under ``auto_tune=True`` tunes too, unless an operand is a
        :class:`MatrixHandle` (its tile is pinned by the warm-cache
        contract; re-tiling behind the caller would break it)."""
        if isinstance(tile, str):
            if tile != "auto":
                raise ValueError(f"tile must be an int or 'auto', "
                                 f"got {tile!r}")
        elif not (tile is None and self._auto_tune and not any(
                isinstance(x, MatrixHandle) for x in operands)):
            return tile
        if dtype is None:
            # tune at the operands' storage precision (it halves/doubles
            # the modeled byte volume); fall back to f64 for exotic
            # legacy dtypes outside the registry
            try:
                dt = _array_of(operands[0]).dtype
                for x in operands[1:]:
                    dt = promote_dtypes(dt, _array_of(x).dtype)
                dtype = canonical_dtype(dt)
            except Exception:
                dtype = np.float64
        return self.auto_tile(routine, m, k, n, dtype=dtype)

    def tuning_report(self) -> Dict[str, object]:
        """Introspection for the autotuner: fingerprint, sweep/cache
        counters split by provenance (file-cache vs process-cache hits,
        model adoptions vs sweeps vs fallbacks), candidate spaces, the
        per-key tuning decisions this context made, and the schedule
        knobs currently applied."""
        with self._lock:
            if self._tuner is None:
                return {"enabled": self._auto_tune,
                        "mode": self._tune_mode,
                        "sweeps": 0, "bucket_sweeps": 0,
                        "confirmations": 0,
                        "cache_hits": 0, "file_cache_hits": 0,
                        "process_cache_hits": 0,
                        "model_adoptions": 0, "model_fallbacks": 0,
                        "cache_entries": 0, "entries": []}
            rep = self._get_tuner().report()
            rep["enabled"] = self._auto_tune
            rep["applied"] = {"tile_default": self.tile_size,
                              "n_streams": self.cfg.n_streams,
                              "policy": self.cfg.policy,
                              "work_centric": self.cfg.work_centric}
            return rep

    # ======================================================== L3 routines
    @_api_call
    def gemm(self, A: ArrayLike, B: ArrayLike, C: Optional[ArrayLike] = None,
             *, alpha: float = 1.0, beta: float = 0.0,
             transa: str = "N", transb: str = "N",
             tile: Optional[int] = None, dtype=None) -> MatrixHandle:
        """C = alpha * op(A) @ op(B) + beta * C   (Eq. 1a)."""
        self._check_open()
        transa, transb = transa.upper()[0], transb.upper()[0]
        dt = self._resolve_dtype(dtype)
        strict = dtype is not None
        with self._lock:
            a_sh, b_sh = _shape_of(A), _shape_of(B)
            tile = self._tile_arg(
                tile, "gemm",
                a_sh[0] if transa == "N" else a_sh[1],
                a_sh[1] if transa == "N" else a_sh[0],
                b_sh[1] if transb == "N" else b_sh[0], dt, (A, B))
            eph: List[MatrixHandle] = []
            Ah = self._coerce(A, "A", tile, eph, dt, strict)
            Bh = self._coerce(B, "B", tile, eph, dt, strict)
            self._check_tiles(Ah, Bh)
            t = Ah.tile
            m = Ah.shape[0] if transa == "N" else Ah.shape[1]
            k = Ah.shape[1] if transa == "N" else Ah.shape[0]
            kb = Bh.shape[0] if transb == "N" else Bh.shape[1]
            n = Bh.shape[1] if transb == "N" else Bh.shape[0]
            if k != kb:
                raise ValueError(f"inner dims mismatch: {k} vs {kb}")
            out_dt = dt if dt is not None else promote_dtypes(
                Ah.array().dtype, Bh.array().dtype)
            self._check_exec_dtype(out_dt, Ah.dtype, Bh.dtype)
            out = self._prep_c(C, (m, n), t, out_dt, beta,
                               force=dt is not None)
            with telemetry.span("blasx.plan"):
                tasks = taskmod.taskize_gemm(Ah.tiled.grid, Bh.tiled.grid,
                                             out.tiled.grid, transa, transb,
                                             alpha, beta)
            mats = {h.matrix_id: h.tiled for h in (Ah, Bh, out)}
            self._run("gemm", tasks, mats, out.matrix_id, eph)
            return out

    @_api_call
    def syrk(self, A: ArrayLike, C: Optional[ArrayLike] = None, *,
             alpha: float = 1.0, beta: float = 0.0, uplo: str = "U",
             trans: str = "N", tile: Optional[int] = None,
             dtype=None) -> MatrixHandle:
        """C = alpha * op(A) @ op(A)^T + beta * C, uplo triangle (Eq. 1b)."""
        self._check_open()
        trans = trans.upper()[0]
        dt = self._resolve_dtype(dtype)
        strict = dtype is not None
        with self._lock:
            a_sh = _shape_of(A)
            nt, kt = (a_sh if trans == "N" else a_sh[::-1])
            tile = self._tile_arg(tile, "syrk", nt, kt, nt, dt, (A,))
            eph: List[MatrixHandle] = []
            Ah = self._coerce(A, "A", tile, eph, dt, strict)
            n = Ah.shape[0] if trans == "N" else Ah.shape[1]
            out_dt = dt if dt is not None else Ah.array().dtype
            self._check_exec_dtype(out_dt, Ah.dtype)
            out = self._prep_c(C, (n, n), Ah.tile, out_dt, beta,
                               force=dt is not None)
            with telemetry.span("blasx.plan"):
                tasks = taskmod.taskize_syrk(Ah.tiled.grid, out.tiled.grid,
                                             uplo, trans, alpha, beta)
            mats = {h.matrix_id: h.tiled for h in (Ah, out)}
            self._run("syrk", tasks, mats, out.matrix_id, eph)
            return out

    @_api_call
    def syr2k(self, A: ArrayLike, B: ArrayLike,
              C: Optional[ArrayLike] = None, *, alpha: float = 1.0,
              beta: float = 0.0, uplo: str = "U", trans: str = "N",
              tile: Optional[int] = None, dtype=None) -> MatrixHandle:
        """C = alpha*(op(A)op(B)^T + op(B)op(A)^T) + beta*C (Eq. 1e)."""
        self._check_open()
        trans = trans.upper()[0]
        dt = self._resolve_dtype(dtype)
        strict = dtype is not None
        with self._lock:
            a_sh = _shape_of(A)
            nt, kt = (a_sh if trans == "N" else a_sh[::-1])
            tile = self._tile_arg(tile, "syr2k", nt, kt, nt, dt, (A, B))
            eph: List[MatrixHandle] = []
            Ah = self._coerce(A, "A", tile, eph, dt, strict)
            Bh = self._coerce(B, "B", tile, eph, dt, strict)
            self._check_tiles(Ah, Bh)
            n = Ah.shape[0] if trans == "N" else Ah.shape[1]
            out_dt = dt if dt is not None else promote_dtypes(
                Ah.array().dtype, Bh.array().dtype)
            self._check_exec_dtype(out_dt, Ah.dtype, Bh.dtype)
            out = self._prep_c(C, (n, n), Ah.tile, out_dt, beta,
                               force=dt is not None)
            with telemetry.span("blasx.plan"):
                tasks = taskmod.taskize_syr2k(Ah.tiled.grid, Bh.tiled.grid,
                                              out.tiled.grid, uplo, trans,
                                              alpha, beta)
            mats = {h.matrix_id: h.tiled for h in (Ah, Bh, out)}
            self._run("syr2k", tasks, mats, out.matrix_id, eph)
            return out

    @_api_call
    def symm(self, A: ArrayLike, B: ArrayLike,
             C: Optional[ArrayLike] = None, *, alpha: float = 1.0,
             beta: float = 0.0, side: str = "L", uplo: str = "U",
             tile: Optional[int] = None, dtype=None) -> MatrixHandle:
        """C = alpha * sym(A) @ B + beta * C (side='L'; Eq. 1f).

        ``side='R'`` reduces to the left-side tile algorithm via the
        §III-C transpose identity; it operates on transposed host
        copies, so cache reuse applies within — not across — the call,
        and the copies are coerced like raw arrays: a context default
        dtype applies to them (a handle's storage precision is only
        preserved on ``side='L'``; pass an explicit per-call ``dtype=``
        to pin the precision on either side).
        """
        self._check_open()
        side = side.upper()[0]
        if side == "R":
            # same handle-ownership/dtype rules as side='L' before the
            # operands degrade to raw transposed copies.  C is exempt
            # on both sides: it only seeds the output (cast freely),
            # it never becomes a cached-tile operand.
            self._check_side_r_handles(dtype, A=A, B=B)
            # C = alpha*B*A + beta*C  ==  (alpha*A*B^T + beta*C^T)^T
            with telemetry.span("blasx.prep"):
                Bt = _host_transpose(_array_of(B))
                Ct = None if C is None else \
                    _host_transpose(_as2d(_array_of(C), "C"))
            out = self.symm(_array_of(A), Bt, Ct, alpha=alpha, beta=beta,
                            side="L", uplo=uplo, tile=tile, dtype=dtype)
            return self._transposed_result(out)
        dt = self._resolve_dtype(dtype)
        strict = dtype is not None
        with self._lock:
            b_sh = _shape_of(B)
            tile = self._tile_arg(tile, "symm", b_sh[0], b_sh[0], b_sh[1],
                                  dt, (A, B))
            eph: List[MatrixHandle] = []
            Ah = self._coerce(A, "A", tile, eph, dt, strict)
            Bh = self._coerce(B, "B", tile, eph, dt, strict)
            self._check_tiles(Ah, Bh)
            m, n = Bh.shape
            if Ah.shape != (m, m):
                raise ValueError(f"A must be ({m},{m}), got {Ah.shape}")
            out_dt = dt if dt is not None else promote_dtypes(
                Ah.array().dtype, Bh.array().dtype)
            self._check_exec_dtype(out_dt, Ah.dtype, Bh.dtype)
            out = self._prep_c(C, (m, n), Ah.tile, out_dt, beta,
                               force=dt is not None)
            with telemetry.span("blasx.plan"):
                tasks = taskmod.taskize_symm(Ah.tiled.grid, Bh.tiled.grid,
                                             out.tiled.grid, uplo, alpha, beta)
            mats = {h.matrix_id: h.tiled for h in (Ah, Bh, out)}
            self._run("symm", tasks, mats, out.matrix_id, eph)
            return out

    @_api_call
    def trmm(self, A: ArrayLike, B: ArrayLike, *, alpha: float = 1.0,
             side: str = "L", uplo: str = "U", transa: str = "N",
             diag: str = "N", tile: Optional[int] = None,
             dtype=None) -> MatrixHandle:
        """B := alpha * op(tri(A)) @ B (side='L'; Eq. 1d), returned as a
        new handle (functional, B is not overwritten)."""
        self._check_open()
        side = side.upper()[0]
        if side == "R":
            self._check_side_r_handles(dtype, A=A, B=B)
            # B*op(A) == (op(A)^T B^T)^T — §III-C at matrix granularity
            flip = "T" if transa.upper()[0] == "N" else "N"
            with telemetry.span("blasx.prep"):
                Bt = _host_transpose(_array_of(B))
            out = self.trmm(_array_of(A), Bt, alpha=alpha, side="L",
                            uplo=uplo, transa=flip, diag=diag, tile=tile,
                            dtype=dtype)
            return self._transposed_result(out)
        dt = self._resolve_dtype(dtype)
        strict = dtype is not None
        with self._lock:
            b_sh = _shape_of(B)
            tile = self._tile_arg(tile, "trmm", b_sh[0], b_sh[0], b_sh[1],
                                  dt, (A, B))
            eph: List[MatrixHandle] = []
            Ah = self._coerce(A, "A", tile, eph, dt, strict)
            Bh = self._coerce(B, "B", tile, eph, dt, strict)
            self._check_tiles(Ah, Bh)
            m, n = Bh.shape
            if Ah.shape != (m, m):
                raise ValueError(f"A must be ({m},{m}), got {Ah.shape}")
            # legacy semantics: TRMM's result keeps B's dtype (unless an
            # explicit dtype= pinned the call's precision)
            out_dt = dt if dt is not None else Bh.array().dtype
            self._check_exec_dtype(out_dt, Ah.dtype, Bh.dtype)
            out = self._fresh_out(m, n, Ah.tile, out_dt)
            # B's tiles are the taskization's Cin inputs: a reused handle
            # serves them straight from the warm cache.
            with telemetry.span("blasx.plan"):
                tasks = taskmod.taskize_trmm(Ah.tiled.grid, Bh.tiled.grid,
                                             out.tiled.grid, uplo, transa,
                                             diag, alpha)
            mats = {h.matrix_id: h.tiled for h in (Ah, Bh, out)}
            self._run("trmm", tasks, mats, out.matrix_id, eph)
            return out

    @_api_call
    def trsm(self, A: ArrayLike, B: ArrayLike, *, alpha: float = 1.0,
             side: str = "L", uplo: str = "U", transa: str = "N",
             diag: str = "N", tile: Optional[int] = None,
             dtype=None) -> MatrixHandle:
        """Solve op(tri(A)) @ X = alpha * B (side='L'; Eq. 1c), or
        X @ op(tri(A)) = alpha * B (side='R'); returns X.

        Both sides run as one tile algorithm, mirrored
        (:func:`~repro.core.task.taskize_trsm`).  ``side='R'`` checks
        handles like ``side='L'``, then tiles their data afresh like raw
        arrays, at the call's tile and dtype."""
        self._check_open()
        side = side.upper()[0]
        if side == "R":
            self._check_side_r_handles(dtype, A=A, B=B)
            A, B = _array_of(A), _array_of(B)
        dt = self._resolve_dtype(dtype)
        strict = dtype is not None
        with self._lock:
            b_sh = _shape_of(B)
            # A is square on B's rows (side L) or on its columns (side R)
            ka, kn = b_sh[::-1] if side == "R" else b_sh
            tile = self._tile_arg(tile, "trsm", ka, ka, kn, dt, (A, B))
            eph: List[MatrixHandle] = []
            Ah = self._coerce(A, "A", tile, eph, dt, strict)
            Bh = self._coerce(B, "B", tile, eph, dt, strict)
            self._check_tiles(Ah, Bh)
            m, n = Bh.shape
            if Ah.shape != (ka, ka):
                raise ValueError(f"A must be ({ka},{ka}), got {Ah.shape}")
            out_dt = dt if dt is not None else promote_dtypes(
                Ah.array().dtype, Bh.array().dtype)
            self._check_exec_dtype(out_dt, Ah.dtype, Bh.dtype)
            out = self._fresh_out(m, n, Ah.tile, out_dt)
            with telemetry.span("blasx.plan"):
                tasks = taskmod.taskize_trsm(Ah.tiled.grid, Bh.tiled.grid,
                                             out.tiled.grid, uplo, transa,
                                             diag, alpha, side=side)
            mats = {h.matrix_id: h.tiled for h in (Ah, Bh, out)}
            self._run("trsm", tasks, mats, out.matrix_id, eph)
            return out

    # --------------------------------------------------------- batched API
    @_api_call
    def gemm_batched(self, As: Sequence[ArrayLike], Bs: Sequence[ArrayLike],
                     Cs: Optional[Sequence[ArrayLike]] = None, *,
                     alpha: float = 1.0, beta: float = 0.0,
                     transa: str = "N", transb: str = "N",
                     tile: Optional[int] = None,
                     dtype=None) -> List[MatrixHandle]:
        """Pointer-array style batch (cublasDgemmBatched analogue)."""
        from .batch import gemm_batched
        return gemm_batched(self, As, Bs, Cs, alpha=alpha, beta=beta,
                            transa=transa, transb=transb, tile=tile,
                            dtype=dtype)

    @_api_call
    def gemm_strided_batched(self, A, B, C=None, *, alpha: float = 1.0,
                             beta: float = 0.0, transa: str = "N",
                             transb: str = "N",
                             tile: Optional[int] = None,
                             dtype=None) -> np.ndarray:
        """3-D strided batch (cublasDgemmStridedBatched analogue)."""
        from .batch import gemm_strided_batched
        return gemm_strided_batched(self, A, B, C, alpha=alpha, beta=beta,
                                    transa=transa, transb=transb, tile=tile,
                                    dtype=dtype)

    # ------------------------------------------------------------- helpers
    def _check_side_r_handles(self, dtype, **operands) -> None:
        """side='R' calls degrade handles to raw arrays (transposed
        copies in trmm and symm); enforce the same ownership and
        dtype-mismatch rules the side='L' coercion path applies, so
        both sides reject an explicit ``dtype=`` that contradicts a
        handle's storage instead of silently recasting.  Like side='L',
        the context default is not enforced against handles — only a
        per-call override is."""
        dt = self._resolve_dtype(dtype) if dtype is not None else None
        for name, x in operands.items():
            if isinstance(x, MatrixHandle):
                self._adopt(x, dt, name)

    def _check_exec_dtype(self, *dts) -> None:
        """Gate inferred dtypes — the output AND every input's storage
        dtype (a half-precision operand crawls through the engine even
        when promotion widens the output) — against the backend.  Only
        registry dtypes with a restricted backend set are checked
        (currently the half precisions, jax/pallas-only —
        ``repro.core.dtypes`` is the source of truth); anything
        outside the registry — legacy exotic dtypes numpy happens to
        promote to — keeps the pre-multi-precision behaviour."""
        for dt in dts:
            allowed = SUPPORTED_DTYPES.get(np.dtype(dt).name)
            if allowed is not None and self.cfg.backend not in allowed:
                validate_backend_dtype(dt, self.cfg.backend)  # raises

    @staticmethod
    def _check_tiles(*handles: "MatrixHandle") -> None:
        tiles = {h.tile for h in handles}
        if len(tiles) > 1:
            names = ", ".join(f"{h.matrix_id}={h.tile}" for h in handles)
            raise ValueError(f"tile mismatch: {names}")

    def _transposed_result(self, out: MatrixHandle) -> MatrixHandle:
        """§III-C side='R' epilogue: re-tile the transposed result and
        drop the intermediate handle's cached tiles — the caller never
        sees it, so they could only ever be dead weight.

        The handle is built directly (like :meth:`_fresh_out`): the
        left-side call already resolved and validated the output dtype,
        and ``tile(dtype=arr.dtype)`` would re-validate it against the
        registry — rejecting legacy exotic result dtypes (e.g. integer
        inputs promoted by the left-side call) that this epilogue must
        preserve as-is."""
        with telemetry.span("blasx.prep"):
            arr = _host_transpose(out.array())
            mid = f"M{next(_MATRIX_IDS)}"
            res = MatrixHandle(self, TiledMatrix(mid, arr, out.tile))
            out.invalidate()
        return res

    def _prep_c(self, C: Optional[ArrayLike], shape, tile: int, dtype,
                beta: float, force: bool = False) -> MatrixHandle:
        if C is None:
            if beta != 0.0:
                raise ValueError("beta != 0 requires C")
            return self._fresh_out(shape[0], shape[1], tile, dtype)
        c = _as2d(_array_of(C), "C")
        if c.shape != shape:
            raise ValueError(f"C shape {c.shape} != {shape}")
        if force:
            # explicit dtype= call: the requested precision wins (C is
            # cast into the output seed; dtype was validated upstream)
            return self._fresh_out(shape[0], shape[1], tile, dtype, seed=c)
        # legacy semantics: the output keeps C's dtype (the runtime
        # downcasts each written tile via astype).  C's dtype IS the
        # real output dtype here, so it — not the promoted out_dt the
        # call site checked — must pass the backend gate: a bf16 C
        # would otherwise put half-precision tiles through the engine.
        self._check_exec_dtype(c.dtype)
        return self._fresh_out(shape[0], shape[1], tile, c.dtype, seed=c)


def _host_transpose(a: np.ndarray) -> np.ndarray:
    """A whole operand's transpose, copied on the host; its bytes go to
    the ``host_transpose_bytes`` counter of ``repro.telemetry``."""
    t = np.ascontiguousarray(a.T)
    telemetry.count("host_transpose_bytes", t.nbytes)
    return t


def _array_of(x: ArrayLike) -> np.ndarray:
    return x.array() if isinstance(x, MatrixHandle) else np.asarray(x)


def _shape_of(x: ArrayLike):
    """2-D shape of an operand without coercing it (tile resolution
    needs dims before tiling can happen)."""
    sh = x.shape if isinstance(x, MatrixHandle) else np.asarray(x).shape
    if len(sh) != 2:
        raise ValueError(f"operand must be 2-D, got shape {sh}")
    return tuple(sh)


# ---------------------------------------------------------- default context
_default_ctx: Optional[BlasxContext] = None
_default_lock = threading.Lock()

# per-backend default contexts: legacy callers opting into an execution
# backend per call (backend="jax") share one warm-cache context per
# backend, mirroring the unnamed default below
_backend_ctxs: Dict[str, BlasxContext] = {}


def default_context() -> BlasxContext:
    """The module-cached context backing the legacy ``blas3`` functions
    and the ``cblas_*`` layer (created on first use, kept warm)."""
    global _default_ctx
    with _default_lock:
        if _default_ctx is None or _default_ctx.closed:
            _default_ctx = BlasxContext(
                RuntimeConfig(n_devices=1, mode="sim"))
        return _default_ctx


def backend_context(backend: str) -> BlasxContext:
    """The module-cached warm context for one execution backend — the
    ``backend=`` analogue of :func:`default_context`, shared by the
    ``blas3`` and ``cblas`` legacy layers so chained per-call usage
    still hits warm tile caches.

    When the requested backend matches the unnamed default context's
    (the usual ``numpy`` case), the *same* context is shared — mixing
    ``gemm(A, B)`` and ``gemm(A, B, backend="numpy")`` must warm one
    tile cache, not two."""
    global _default_ctx
    with _default_lock:
        d = _default_ctx
        if d is not None and not d.closed and d.cfg.backend == backend:
            return d
        ctx = _backend_ctxs.get(backend)
        if ctx is None or ctx.closed:
            ctx = BlasxContext(RuntimeConfig(n_devices=1, mode="sim",
                                             backend=backend))
            if backend == "numpy" and (d is None or d.closed):
                # this IS the default config; claim the default slot so a
                # later default_context() shares the same warm caches
                _default_ctx = ctx
            else:
                _backend_ctxs[backend] = ctx
        return ctx


def set_default_context(ctx: Optional[BlasxContext]) -> Optional[BlasxContext]:
    """Swap the process-wide default context; returns the previous one
    (not closed — the caller decides its fate)."""
    global _default_ctx
    with _default_lock:
        prev, _default_ctx = _default_ctx, ctx
        return prev
