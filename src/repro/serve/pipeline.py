"""Host-side serving pipeline: AOT prefill/decode buckets + async bookkeeping.

Five pieces, all host machinery (nothing here traces into a jit):

  * `PrefillLadder` — the fixed set of prompt-length buckets the engine
    compiles AHEAD of traffic.  Admission rounds every prompt up to the
    smallest covering bucket, so the jit cache is warmed once at engine
    construction and no XLA compilation ever happens under traffic.  The
    auto ladder is powers-of-two multiples of the 8-token DCT block capped
    at max_seq (8, 16, 32, ..., max_seq); an explicit ladder narrows it,
    and a prompt that fits no bucket raises — never a silent compile.

  * `DecodeLadder` — the paged engine's context-length buckets.  Each
    bucket owns a jitted decode step whose attend covers a static
    `bucket // 8`-entry slice of the block table; the engine picks the
    smallest bucket covering the deepest live slot's flushed watermark at
    every dispatch, so decode-step cost scales with OCCUPIED context
    instead of pool capacity.  All buckets are warmed at construction
    exactly like the prefill ladder (zero jit traces under traffic), and
    the slice is an exact no-op on outputs: dropped table entries can only
    name blocks the watermark masks anyway.

  * `BackgroundWorker` — a daemon thread draining a backlog queue of
    bookkeeping closures (token appends, latency accounting, returning a
    retired slot's pages to the free list).  The serve loop hands finished
    host work here so the device never waits on Python bookkeeping between
    decode steps (the MaxText offline-inference idiom, adapted to the
    paged pool where retirement must also release pages).  `flush()` is
    the synchronization point: admission blocked on free pages flushes the
    backlog before deciding the pool is really exhausted.

  * `TraceCounts` / `counting` — per-callable jit-trace counters.  The
    wrapped function body increments its counter as a trace-time side
    effect, so `counts` advances exactly when XLA (re)compiles.  The
    zero-compile-under-traffic regression test snapshots the counts after
    warmup and asserts serving moves none of them.

  * `Span` / `GcSpans` — host spans: `jax.profiler.TraceAnnotation`s with
    integer metadata that land in the same profile as the device ops, and
    the engine's time counters, each the summed duration of one span.
"""
from __future__ import annotations

import gc
import queue
import threading
import time
from dataclasses import dataclass

from jax.profiler import TraceAnnotation

BLOCK = 8  # the DCT seq-block; ladder buckets are multiples of it


# ---------------------------------------------------------------------------
# AOT prefill bucket ladder
# ---------------------------------------------------------------------------

def auto_buckets(max_seq: int) -> tuple[int, ...]:
    """Powers-of-two multiples of BLOCK capped at max_seq, max_seq included.

    max_seq=48 -> (8, 16, 32, 48); max_seq=64 -> (8, 16, 32, 64).
    """
    assert max_seq % BLOCK == 0 and max_seq >= BLOCK, max_seq
    out = []
    b = BLOCK
    while b < max_seq:
        out.append(b)
        b *= 2
    out.append(max_seq)
    return tuple(out)


@dataclass(frozen=True)
class PrefillLadder:
    """The fixed prompt-length buckets admission rounds up to."""

    buckets: tuple[int, ...]

    @classmethod
    def build(cls, max_seq: int, buckets=None) -> "PrefillLadder":
        if buckets is None:
            return cls(auto_buckets(max_seq))
        buckets = tuple(sorted(int(b) for b in buckets))
        if not buckets:
            raise ValueError("empty prefill ladder")
        for b in buckets:
            if b % BLOCK or b < BLOCK:
                raise ValueError(f"ladder bucket {b} is not a multiple of {BLOCK}")
        if buckets[-1] > max_seq:
            raise ValueError(
                f"ladder bucket {buckets[-1]} exceeds max_seq={max_seq}")
        return cls(buckets)

    def bucket_for(self, prompt_len: int) -> int:
        """Smallest bucket covering `prompt_len`; raises off-ladder.

        The raise is the explicit alternative to silently jit-compiling a
        fresh prefill under traffic: the caller either re-buckets the
        workload or widens the ladder, both ahead of time.
        """
        for b in self.buckets:
            if prompt_len <= b:
                return b
        raise ValueError(
            f"prompt of {prompt_len} tokens fits no prefill bucket "
            f"{self.buckets}: off-ladder admission would compile under "
            f"traffic (widen prefill_buckets or raise max_seq)")

    def row_counts(self, batch: int) -> tuple[int, ...]:
        """Admission-batch row counts the engine pads to: powers of two up
        to `batch`, plus `batch` itself — the full warmup set."""
        out = []
        r = 1
        while r < batch:
            out.append(r)
            r *= 2
        out.append(batch)
        return tuple(out)

    def pad_rows(self, n: int, batch: int) -> int:
        """Round an admission group of n requests up to a warmed row count."""
        for r in self.row_counts(batch):
            if n <= r:
                return r
        return batch


# ---------------------------------------------------------------------------
# Decode-bucket ladder (paged pool)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DecodeLadder:
    """Context-length buckets the paged decode step is compiled at.

    A bucket of T tokens means a decode step whose attend reads only the
    first T // 8 block-table entries (a static slice — see
    core.kv_cache.table_view).  The ladder always ends at max_seq, so any
    legal flushed watermark has a covering bucket; `bucket_for` never
    raises under traffic the pool itself can hold.
    """

    buckets: tuple[int, ...]

    @classmethod
    def build(cls, max_seq: int, buckets=None) -> "DecodeLadder":
        if buckets is None:  # auto: powers-of-two x BLOCK, max_seq included
            return cls(auto_buckets(max_seq))
        if buckets is False or buckets == "off":
            return cls((max_seq,))  # single full-capacity bucket (pre-ladder)
        buckets = tuple(sorted({int(b) for b in buckets}))
        if not buckets:
            raise ValueError("empty decode ladder")
        for b in buckets:
            if b % BLOCK or b < BLOCK:
                raise ValueError(
                    f"decode bucket {b} is not a multiple of {BLOCK}")
        if buckets[-1] > max_seq:
            raise ValueError(
                f"decode bucket {buckets[-1]} exceeds max_seq={max_seq}")
        if buckets[-1] < max_seq:
            buckets = buckets + (max_seq,)  # must always cover a full pool
        return cls(buckets)

    def bucket_for(self, context_tokens: int) -> int:
        """Smallest bucket covering `context_tokens` of flushed context."""
        for b in self.buckets:
            if context_tokens <= b:
                return b
        raise ValueError(
            f"flushed context of {context_tokens} tokens exceeds the decode "
            f"ladder {self.buckets} — deeper than the pool itself")


# ---------------------------------------------------------------------------
# Jit-trace accounting
# ---------------------------------------------------------------------------

class TraceCounts(dict):
    """name -> number of times the named callable was traced by jit."""

    def snapshot(self) -> dict:
        return dict(self)

    def delta(self, since: dict) -> dict:
        return {k: v - since.get(k, 0) for k, v in self.items()
                if v != since.get(k, 0)}


def counting(name: str, counts: TraceCounts, fn):
    """Wrap `fn` so tracing it (and thus compiling it) bumps counts[name].

    The increment runs when the *python* body runs — under jit that is once
    per trace, never per execution — so the counter is a compile counter.
    """
    counts.setdefault(name, 0)

    def wrapped(*args, **kwargs):
        counts[name] += 1
        return fn(*args, **kwargs)

    wrapped.__name__ = f"traced_{name}"
    return wrapped


# ---------------------------------------------------------------------------
# Host spans
# ---------------------------------------------------------------------------

class Span(TraceAnnotation):
    """A host span: a `TraceAnnotation` (integer metadata only; the name
    and metadata are encoded only while a profiler is recording) whose
    duration, on the profiler's clock (`time.time_ns`), is added to
    `stats[k]` for each k in `keys` when it closes."""

    def __init__(self, name: str, stats: dict | None = None,
                 keys: tuple = (), **meta):
        super().__init__(name, **meta)
        self._stats, self._keys = stats, keys

    def __enter__(self):
        # the clock brackets the annotation's own enter and exit, so the
        # counted time covers the span the profile records
        self._t0 = time.time_ns()
        super().__enter__()
        return self

    def __exit__(self, *exc):
        out = super().__exit__(*exc)
        dt = (time.time_ns() - self._t0) * 1e-9
        for k in self._keys:
            self._stats[k] += dt
        return out


class GcSpans:
    """While entered, each collection of Python's garbage collector is a
    `python.gc` span (metadata: the generation collected), so a collector
    pause has a name in the profile."""

    def __init__(self):
        self._open = None

    def __call__(self, phase, info):
        if phase == "start" and self._open is None:
            self._open = TraceAnnotation("python.gc",
                                         generation=info["generation"])
            self._open.__enter__()
        elif phase == "stop" and self._open is not None:
            span, self._open = self._open, None
            span.__exit__(None, None, None)

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)
        self("stop", None)


# ---------------------------------------------------------------------------
# Background bookkeeping worker
# ---------------------------------------------------------------------------

class BackgroundWorker:
    """Daemon thread running bookkeeping closures from a backlog queue.

    The serve loop submits closures (append tokens, record latency, return
    pages); the worker runs them strictly in submission order, so
    per-request token order and free-list state are deterministic.  Errors
    are captured and re-raised on the serve thread at the next `flush()` /
    `close()` — a bookkeeping bug must fail the request loop, not vanish
    in a thread.  Each closure runs inside a `serve.bookkeeping` span, and
    each `flush()` the serve thread blocks on is a `serve.worker_flush`
    span."""

    def __init__(self, name: str = "serve-bookkeeping"):
        self._q: queue.Queue = queue.Queue()
        self._err: BaseException | None = None
        self._thread = threading.Thread(target=self._run, name=name,
                                        daemon=True)
        self._thread.start()

    def _run(self):
        while True:
            item = self._q.get()
            try:
                if item is None:
                    return
                if self._err is None:
                    with TraceAnnotation("serve.bookkeeping"):
                        item()
            except BaseException as e:  # surfaced at flush()/close()
                self._err = e
            finally:
                self._q.task_done()

    def submit(self, fn) -> None:
        if self._err is not None:
            self._reraise()
        self._q.put(fn)

    def flush(self) -> None:
        """Block until every submitted closure has run; re-raise errors."""
        with TraceAnnotation("serve.worker_flush"):
            self._q.join()
        if self._err is not None:
            self._reraise()

    def close(self) -> None:
        self._q.join()
        self._q.put(None)
        self._thread.join()
        if self._err is not None:
            self._reraise()

    def _reraise(self):
        err, self._err = self._err, None
        raise err


# ---------------------------------------------------------------------------
# Engine warmup: compile the whole serving surface before traffic
# ---------------------------------------------------------------------------

def warmup_engine(engine) -> float:
    """AOT-compile every (rows x bucket) admission shape plus the decode /
    splice / reset / fix steps the continuous scheduler can issue.

    Runs real dummy calls (the only way the pinned jax version is
    guaranteed to populate the jit executable cache) against a scratch
    pool; every splice targets out-of-range slots/pages, so a warmed
    engine's first real pool is still all-zeros.  Returns wall seconds;
    the engine accounts them as `stats["warmup_s"]`, never as prefill or
    decode time.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    t0 = time.perf_counter()
    temp = engine.sc.temperature > 0.0
    rng = jax.random.PRNGKey(0)  # warmup never touches the engine's stream
    cache = engine._cache_init(engine.batch)
    # host-built inputs, placed as the serve loop places its own: under
    # `jax.set_mesh` a jnp constructor would commit them replicated, which
    # the decode/fix jits pinned to the mesh's data axes refuse
    zeros_b = engine.device_vec(np.zeros((engine.batch,), np.int32))
    ladder = engine.ladder
    nb_table = engine.sc.max_seq // BLOCK
    for bucket in ladder.buckets:
        for rows in ladder.row_counts(engine.batch):
            tokens = jnp.asarray(np.zeros((rows, bucket), np.int32))
            lengths = jnp.asarray(np.full((rows,), bucket, np.int32))
            args = [engine.params, tokens, lengths] + ([rng] if temp else [])
            first, slot_cache = engine._admit_step(*args)
            drop_slots = jnp.asarray(np.full((rows,), engine.batch, np.int32))
            if engine.paged:
                page_ids = jnp.asarray(np.full((rows, bucket // BLOCK),
                                               engine._n_pages, np.int32))
                table_rows = jnp.asarray(np.zeros((rows, nb_table), np.int32))
                cache = engine._write(cache, slot_cache, drop_slots,
                                      page_ids, table_rows)
            else:
                cache = engine._write(cache, slot_cache, drop_slots)
            if engine.paged and engine._prefix is not None:
                # prefix verification runs at every admission shape
                engine._match(cache, slot_cache,
                              page_ids).block_until_ready()
            first.block_until_ready()
    # decode + slot lifecycle steps.  A paged engine owns one decode jit
    # per ladder bucket (static table-slice width) — warm every one; the
    # dense engine has a single decode shape.
    step_args = [engine.params, zeros_b, cache, zeros_b]
    if engine.paged:
        step_args.append(jnp.asarray(
            np.full((engine.batch,), engine._n_pages, np.int32)))
    if temp:
        step_args.append(rng)
    if engine.paged:
        for fn in engine._decode_fns.values():
            tok, pos1, cache = fn(*step_args)
            step_args[2] = cache  # the step may consume (donate) its pool
    else:
        tok, pos1, cache = engine._decode(*step_args)
    if engine.paged and engine._tier is not None:
        # tier fault path: one spill gather + one restore splice per
        # prefill-ladder width, driven EXACTLY as the engine issues them at
        # park/resume time — numpy host trees in (host pages live outside
        # any mesh), pool-sharded cache out — so a tiered engine never
        # compiles under traffic either. All page ids are out-of-range:
        # the warmup restore writes nothing.
        for bucket in ladder.buckets:
            nbkt = bucket // BLOCK
            ids = np.full((nbkt,), engine._n_pages, np.int32)
            upd = engine._spill(cache, jnp.int32(0), jnp.asarray(ids))
            upd = jax.tree.map(np.asarray, upd)
            cache = engine._restore(
                cache, upd, jnp.int32(0), jnp.asarray(ids),
                jnp.asarray(np.zeros((nb_table,), np.int32)))
    cache = engine._reset(cache, jnp.int32(0))
    drop_idx = jnp.asarray(np.full((engine.batch,), engine.batch, np.int32))
    fix_vals = jnp.asarray(np.zeros((engine.batch,), np.int32))
    tok, pos1 = engine._fix(tok, pos1, drop_idx, fix_vals, fix_vals)
    tok.block_until_ready()
    del cache
    np.asarray(tok)  # drain
    return time.perf_counter() - t0
