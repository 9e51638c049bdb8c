"""Page tiers for the paged compressed KV pool: host offload + prefix sharing.

Two host-side pieces, both pure allocator state (nothing here traces into a
jit — the device only ever sees page ids and update trees the engine hands
it, exactly like the free list that PR 5 introduced):

  * `TierManager` — the off-chip half of the paper's memory hierarchy. It
    owns a pinned numpy backing store shaped like the device pool's packed /
    scale planes but `host_pages` deep, plus its own free list. When the
    engine's watermark policy evicts (parks) a victim slot, the slot's
    fully-flushed pages are gathered off the device in ONE bucketed jit
    (`kv_cache.paged_gather_slot`), copied into host pages on the
    `BackgroundWorker` (overlapped with decode, one step deep), and the
    device pages return to the free list. The fault path is the inverse:
    a parked slot resumes by streaming its host pages back through one
    `paged_write_slot` jit BEFORE its next attend — the engine only marks a
    slot live again after the restore is dispatched, and the decode bucket
    ladder makes "which pages are attendable" exact, so the prefetch is
    provable rather than heuristic. Pages hold compressed int8 DCT blocks +
    f32 scales, so a spill moves ~6-16x fewer bytes than raw K/V — the
    EBPC argument that compressed transfers make the DRAM tier affordable.

  * `PrefixIndex` — content addressing for copy-on-write prefix sharing.
    `prefix_block_keys` chains a blake2b digest over each full 8-token
    prompt block, so key j commits to tokens[0:8*(j+1)] — exactly the
    inputs block j's K/V depends on under causal attention with absolute
    rope. Admission looks up the longest leading run of device-resident
    hits and reserves pages only for the unshared suffix; the engine then
    VERIFIES candidate pages bitwise on device (`paged_rows_match`) before
    trusting them, so a hash collision can only ever cost a demotion (copy
    into fresh pages), never alias two different prefixes. The index maps
    key <-> page both ways: a page is dropped from the index the moment it
    is freed or spilled (host pages are not shareable), and re-registered
    when a parked slot's restore brings the same bytes back.

The tier bit itself lives host-side, with the allocator: the engine's
per-slot page lists and parked-slot records know whether a logical block is
device- or host-resident, while device block tables only ever contain
device page ids (a parked slot's table row is zeroed, and rebuilt by the
restore). Keeping the bit out of the jitted tables is what lets every
existing decode/attend jit run unchanged — tiering is pure allocator
policy, like the free list before it.
"""
from __future__ import annotations

import hashlib

import numpy as np

from repro.core import page_rows

BLOCK = 8  # tokens per page (the DCT seq-block)

# The dct family's page planes — kept as a module constant for callers/tests
# that reason about the default family. TierManager itself derives each
# segment's plane set from the segment's row layout (codec families differ:
# bitplane pages also carry bpmask/blen planes), so mixed-codec plans tier
# correctly.
PAGE_KEYS = ("packed_k", "scale_k", "packed_v", "scale_v")
TAIL_KEYS = ("tail_k", "tail_v")


# ---------------------------------------------------------------------------
# Prefix hashing
# ---------------------------------------------------------------------------

def prefix_block_keys(prompt: np.ndarray) -> list[bytes]:
    """Chained content keys for every FULL 8-token block of `prompt`.

    keys[j] is a blake2b digest over tokens[0 : 8*(j+1)] — the whole prefix
    through block j, not just block j's own tokens. Block j's K/V is a pure
    function of exactly that prefix (causal attention, absolute rope), so
    two prompts agreeing on keys[0..j] computed the same K/V for those
    blocks — up to hash collision, which the engine closes by verifying
    candidate pages bitwise on device before sharing them.

    Only full blocks get keys (a partial block lives in the raw tail ring
    and is never paged), and the result depends on nothing but the prompt
    tokens themselves — not the admission bucket, the batch row the prompt
    lands in, or any padding (pinned by a hypothesis property test).
    """
    arr = np.ascontiguousarray(np.asarray(prompt, dtype=np.int32))
    h = hashlib.blake2b(digest_size=16)
    keys = []
    for j in range(len(arr) // BLOCK):
        h.update(arr[j * BLOCK:(j + 1) * BLOCK].tobytes())
        keys.append(h.digest())
    return keys


class PrefixIndex:
    """key <-> device-page bimap behind copy-on-write prefix sharing.

    `key_fn` is injectable so tests can force collisions and prove the
    device-side bitwise verification (not the hash) is what prevents
    aliasing. Registration is first-writer-wins: once a key names a page,
    later identical prefixes share that page instead of re-registering.
    """

    def __init__(self, key_fn=prefix_block_keys):
        self.key_fn = key_fn
        self._by_key: dict[bytes, int] = {}
        self._by_page: dict[int, bytes] = {}

    def __len__(self) -> int:
        return len(self._by_key)

    def lookup_run(self, keys: list[bytes]) -> list[int]:
        """Pages for the longest LEADING run of registered keys.

        Sharing must stop at the first miss: block j's reuse is only sound
        when every block before it is shared too (the chained key encodes
        that, but the run guard keeps a later accidental hit from creating
        a hole in the slot's table).
        """
        pages = []
        for key in keys:
            page = self._by_key.get(key)
            if page is None:
                break
            pages.append(page)
        return pages

    def register(self, key: bytes, page: int) -> None:
        if key in self._by_key:  # first writer wins
            return
        self._by_key[key] = page
        self._by_page[page] = key

    def drop_page(self, page: int) -> None:
        """Forget a page (freed or spilled to host) — both directions."""
        key = self._by_page.pop(page, None)
        if key is not None:
            self._by_key.pop(key, None)


# ---------------------------------------------------------------------------
# Host page pool
# ---------------------------------------------------------------------------

class TierManager:
    """Host (off-device) page pool + free list for spilled compressed pages.

    The backing store holds a page's planes in the family's plane form —
    the form the spill gather unpacks the device rows into and the restore
    packs back — with `host_pages` on the page axis: per segment
    ``packed_k/v (Lseg, HP, Hkv, hd/8, k, k) int8`` and
    ``scale_k/v (Lseg, HP, Hkv, hd/8) f32`` for dct — plain numpy, outside
    any mesh (the parallel/sharding helpers only ever see the restored
    update on its way back in). Allocation is id-based like the engine's device free
    list; content moves in `stage_out` (worker thread) and `read_back`
    (admission path, after a `worker.flush()` barrier, so a parked slot's
    bytes are always complete before they stream back).
    """

    def __init__(self, cache_shapes, host_pages: int):
        assert host_pages >= 1, host_pages
        self.host_pages = int(host_pages)
        self._free = list(range(self.host_pages))
        self._store: list[dict[str, np.ndarray]] = []
        self._page_keys: list[tuple[str, ...]] = []
        for seg in cache_shapes.segments:
            shapes = page_rows.segment_layout(seg).plane_shapes()
            planes = {key: np.zeros((seg.stop - seg.start, self.host_pages)
                                    + shape, dtype=np.dtype(dt))
                      for key, (shape, dt) in shapes.items()}
            self._store.append(planes)
            self._page_keys.append(tuple(sorted(planes)))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.host_pages - len(self._free)

    def nbytes(self) -> int:
        return sum(int(a.nbytes) for planes in self._store
                   for a in planes.values())

    def alloc(self, n: int) -> list[int]:
        if n > len(self._free):
            raise RuntimeError(
                f"host page pool exhausted: need {n}, free {len(self._free)}"
                f" of {self.host_pages}")
        return [self._free.pop() for _ in range(n)]

    def release(self, host_ids: list[int]) -> None:
        self._free.extend(host_ids)

    def stage_out(self, host_ids: list[int], update) -> None:
        """Copy gathered page content into host pages (worker thread).

        `update` is the numpy-ified `paged_gather_slot` tree; entry i of
        its page axis corresponds to host_ids[i]. Runs off the serve
        thread; the engine's `worker.flush()` before any read_back is the
        completion barrier.
        """
        for planes, keys, upd in zip(self._store, self._page_keys, update):
            for key in keys:
                src = np.asarray(upd[key])  # (Lseg, 1, nbkt, ...)
                for i, hid in enumerate(host_ids):
                    planes[key][:, hid] = src[:, 0, i]

    def read_back(self, entries: list[tuple[int, int]], nbkt: int):
        """Assemble the restore update for `paged_write_slot`.

        `entries` are (position, host_id) pairs: the host page streams back
        into page-axis position `position` of an (Lseg, 1, nbkt, ...)
        update (positions past the parked slot's host blocks stay zero and
        carry out-of-range page ids, so the scatter drops them). Tails are
        the caller's (they live in the parked record, not the page pool).
        """
        out = []
        for planes, keys in zip(self._store, self._page_keys):
            upd = {}
            for key in keys:
                ref = planes[key]
                buf = np.zeros((ref.shape[0], 1, nbkt) + ref.shape[2:],
                               dtype=ref.dtype)
                for pos, hid in entries:
                    buf[:, 0, pos] = ref[:, hid]
                upd[key] = buf
            out.append(upd)
        return out
