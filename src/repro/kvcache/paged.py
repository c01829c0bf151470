"""Paged KV block pool (vLLM-style, paper §2/§5.1: "RAGCache stores the
key-value tensors in non-contiguous memory blocks").

The pool owns a big (L, n_blocks, KV, block_size, hd) buffer per tier;
documents hold block-id lists.  Ref-counting lets overlapping knowledge-tree
paths share blocks.  ``put``/``gather`` convert between paged storage and
the contiguous (L, 1, T, KV, hd) segment layout the model functions, the
host tier and the disk tier use.  The pool is head-major — each page of one
KV head is a contiguous (block_size, hd) tile — because that is the tile
the paged Pallas kernels stream, and the TPU compiler only accepts a block
whose two minor dims are (page, hd).

``DiskSegmentStore`` is the third tier below the dense host copies: one
mmap file per knowledge-tree node (docs/ARCHITECTURE.md §2).  Segments are
written once on host->disk demotion and the file stays live until the disk
tier evicts the node, so repeated host demotions of the same node move zero
bytes ("spill-only-once", mirroring swap-out-only-once one tier up).
"""
from __future__ import annotations

import dataclasses
import itertools
import os
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def gather_slots(pages, blk, slot):
    """Token-level read of pool planes: ``pages`` (L, n_blocks, KV, block,
    hd); ``blk``/``slot`` int arrays of one shape S -> (L, *S, KV, hd)."""
    xp = np if isinstance(pages, np.ndarray) else jnp
    # a slice between the two index arrays puts their dims first
    return xp.moveaxis(pages[:, blk, :, slot], np.ndim(blk), 0)


def scatter_slots(pages, blk, slot, vals):
    """Token-level write, the inverse of ``gather_slots``: ``vals``
    (L, *S, KV, hd) lands at ``(blk, slot)``.  Returns the updated planes
    (numpy planes are written in place)."""
    if isinstance(pages, np.ndarray):
        pages[:, blk, :, slot] = np.moveaxis(np.asarray(vals), 0, np.ndim(blk))
        return pages
    vals = jnp.moveaxis(vals, 0, np.ndim(blk)).astype(pages.dtype)
    return pages.at[:, blk, :, slot].set(vals)


def max_write_runs(rows: int, block: int, segments: int = 1) -> int:
    """The most pages one chunk row of ``rows`` tokens can write, when its
    tokens cross at most ``segments`` segments.

    A segment's pages are its own and filled in order, so a row writes one
    run per page it touches.  The row's first segment may resume mid-page,
    at a slot ``s0 <= block - 1``; every later segment starts a fresh page.
    Tokens ``t_0 + ... + t_{g-1} <= rows`` then touch at most
    ``ceil((s0 + t_0) / block) + sum_{i>0} ceil(t_i / block)
    <= (rows + (g + 1) * (block - 1)) // block`` pages, and never more
    pages than tokens.  A chunked splitter (``prefill_piece_sizes`` with
    chunk > 0, or one piece per segment) gives ``g = 1``."""
    return min(rows, (rows + (segments + 1) * (block - 1)) // block)


def run_starts(blk, slot, valid):
    """Where each write run of a chunk row begins: (..., S) bool over the
    row's tokens.  A run is the valid tokens that go to one page; one starts
    at the row's first token, at slot 0, and wherever the page changes.
    Works on numpy and jax arrays alike."""
    xp = np if isinstance(blk, np.ndarray) else jnp
    first = xp.arange(blk.shape[-1]) == 0
    prev = xp.concatenate([blk[..., :1], blk[..., :-1]], axis=-1)
    return valid & (first | (slot == 0) | (blk != prev))


def write_pages(pages, li, write_blk, write_slot, q_len, vals, n_runs: int):
    """Write chunk KV into layer ``li`` of the pool a whole page at a time.

    pages: (L, n_blocks, KV, block, hd) pool plane.  write_blk/write_slot:
    (B, S) page coordinates of each row's tokens, of which the first
    ``q_len[b]`` are valid.  vals: (B, S, KV, hd).  n_runs: the static
    number of runs written per row, at least each row's count of
    ``run_starts`` (``max_write_runs`` bounds it).

    Each run reads its page, puts the run's tokens at their slots, keeps
    every other slot, and writes the page back with one
    ``dynamic_update_slice`` that covers the whole (KV, block, hd) window
    and indexes only the two leading dims.  That is a write in the pool's
    own layout: a token-level scatter (``scatter_slots``) makes the TPU
    compiler copy the whole pool into another tiling and back, once per
    layer.  The runs are written one after another, each reading the pool
    as the runs before it left it, so a run with no tokens — a row's spare
    runs, all of a padding row's — writes its page back unchanged; it aims
    at the page of the row's last token, the store's scratch block
    whenever the row is padded.  The result equals the token scatter's
    bit for bit on every page the valid tokens write."""
    B, S = write_blk.shape
    KV, block, hd = pages.shape[2:]
    valid = jnp.arange(S)[None] < q_len[:, None]
    start = run_starts(write_blk, write_slot, valid)
    run = jnp.cumsum(start, axis=1) - 1                        # (B, S)
    member = (run[..., None] == jnp.arange(n_runs)) & valid[..., None]
    length = member.sum(axis=1)                                # (B, P)
    first = jnp.where(length > 0, jnp.argmax(member, axis=1), S - 1)
    blk = jnp.take_along_axis(write_blk, first, axis=1)
    slot0 = jnp.take_along_axis(write_slot, first, axis=1)
    # rows in page layout, padded a page each side so that the page-long
    # window of token ``first - slot0`` is in range for any slot0 < block
    rows = jnp.pad(vals.astype(pages.dtype).transpose(0, 2, 1, 3),
                   ((0, 0), (0, 0), (block, block), (0, 0)))
    slots = jnp.arange(block)
    for b in range(B):
        for r in range(n_runs):
            at = (li, blk[b, r], 0, 0, 0)
            new = jax.lax.dynamic_slice(
                rows[b], (0, first[b, r] - slot0[b, r] + block, 0),
                (KV, block, hd))
            old = jax.lax.dynamic_slice(pages, at, (1, 1, KV, block, hd))[0, 0]
            keep = (slots < slot0[b, r]) | (slots >= slot0[b, r] + length[b, r])
            page = jnp.where(keep[None, :, None], old, new)
            pages = jax.lax.dynamic_update_slice(pages, page[None, None], at)
    return pages


class OutOfBlocks(RuntimeError):
    pass


class BlockPool:
    """Fixed-capacity block allocator with refcounts."""

    def __init__(self, n_blocks: int, block_size: int):
        self.n_blocks = n_blocks
        self.block_size = block_size
        self._free: List[int] = list(range(n_blocks - 1, -1, -1))
        self._ref = np.zeros(n_blocks, np.int32)

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def blocks_for_tokens(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_size)

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise OutOfBlocks(f"need {n}, have {len(self._free)}")
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._ref[b] = 1
        return out

    def incref(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            assert self._ref[b] > 0
            self._ref[b] += 1

    def decref(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            assert self._ref[b] > 0, f"double free of block {b}"
            self._ref[b] -= 1
            if self._ref[b] == 0:
                self._free.append(b)

    def exclusive(self, blocks: Sequence[int]) -> int:
        """How many of ``blocks`` have refcount 1 — i.e. would actually
        return to the free list if their sole owner dropped them."""
        return int(sum(1 for b in blocks if self._ref[b] == 1))

    def check(self) -> None:
        live = int((self._ref > 0).sum())
        assert live + len(self._free) == self.n_blocks
        assert len(set(self._free)) == len(self._free)


class PagedKVStore:
    """Paged storage for per-document KV segments.

    Layout: k/v buffers of shape (L, n_blocks, KV, block_size, hd).  A stored
    segment is (block_ids, n_tokens).  numpy backing doubles as the host tier;
    jnp backing is the device tier.
    """

    def __init__(self, n_layers: int, n_blocks: int, block_size: int,
                 n_kv: int, head_dim: int, dtype=np.float32,
                 device: bool = False, sharding=None):
        self.pool = BlockPool(n_blocks, block_size)
        self.block_size = block_size
        shape = (n_layers, n_blocks, n_kv, block_size, head_dim)
        self.device = device
        # Where the device planes live: None = the default device; a
        # SingleDeviceSharding pins one replica's pool to its own chip; a
        # NamedSharding over the KV-head dim (launch/sharding.py::
        # pool_kv_spec) splits it for tensor-parallel serving.  The planes
        # are created in place and everything written into them (put/append)
        # lands there, so no plane is ever materialized on another device.
        self.sharding = sharding if self.device else None
        if self.device:
            self.k = jnp.zeros(shape, dtype, device=self.sharding)
            self.v = jnp.zeros(shape, dtype, device=self.sharding)
        else:
            self.k = np.zeros(shape, dtype)
            self.v = np.zeros(shape, dtype)

    def _place_segment(self, k_seg, v_seg):
        """Promotion path of a placed pool: copy an incoming host (numpy)
        (L, B, T, KV, hd) segment straight to where the pool lives.  A
        sharded pool gets it with its KV heads split the same way, so the
        host->device copy is BATCHED per mesh-axis member — each device
        receives exactly its head slice, instead of a full replica that the
        next pool write would reshard collectively."""
        if self.sharding is None or not isinstance(k_seg, np.ndarray):
            # default-device pool, or a device-computed segment (prefill
            # cache slice) that is already where it was computed
            return k_seg, v_seg
        seg_sh = self.sharding
        if isinstance(seg_sh, jax.sharding.NamedSharding):
            seg_sh = jax.sharding.NamedSharding(
                seg_sh.mesh,
                jax.sharding.PartitionSpec(None, None, None, seg_sh.spec[2]))
        return jax.device_put(k_seg, seg_sh), jax.device_put(v_seg, seg_sh)

    def bytes_per_token(self) -> int:
        L, _, KV, _, hd = self.k.shape
        return int(2 * L * KV * hd * self.k.dtype.itemsize)

    def put(self, k_seg, v_seg, reserve_tokens: int = 0) -> "PagedSegment":
        """k_seg/v_seg: (L, 1, T, KV, hd) contiguous -> paged blocks.

        reserve_tokens: allocate capacity for this many *extra* tokens beyond
        T (the serving runtime's decode step writes appended tokens into the
        reserved tail slots through the request's block table)."""
        T = k_seg.shape[2]
        nb = self.pool.blocks_for_tokens(T + reserve_tokens)
        blocks = self.pool.alloc(nb)
        pad = nb * self.block_size - T
        if self.device:
            k_seg, v_seg = self._place_segment(k_seg, v_seg)
            ks = jnp.pad(k_seg[:, 0], ((0, 0), (0, pad), (0, 0), (0, 0)))
            vs = jnp.pad(v_seg[:, 0], ((0, 0), (0, pad), (0, 0), (0, 0)))
            ks = ks.reshape(ks.shape[0], nb, self.block_size,
                            *ks.shape[2:]).swapaxes(2, 3)
            vs = vs.reshape(vs.shape[0], nb, self.block_size,
                            *vs.shape[2:]).swapaxes(2, 3)
            idx = jnp.asarray(blocks)
            self.k = self.k.at[:, idx].set(ks.astype(self.k.dtype))
            self.v = self.v.at[:, idx].set(vs.astype(self.v.dtype))
        else:
            k_seg = np.asarray(k_seg)
            v_seg = np.asarray(v_seg)
            for bi, b in enumerate(blocks):
                lo = bi * self.block_size
                hi = min(lo + self.block_size, T)
                if hi <= lo:            # reserve-only tail block
                    break
                self.k[:, b, :, : hi - lo] = k_seg[:, 0, lo:hi].swapaxes(1, 2)
                self.v[:, b, :, : hi - lo] = v_seg[:, 0, lo:hi].swapaxes(1, 2)
        return PagedSegment(self, blocks, T)

    def append(self, seg: "PagedSegment", k_new, v_new) -> "PagedSegment":
        """Extend an existing segment with more tokens (chunked-prefill
        continuation): fill the partially-used tail slots of the last block,
        then allocate additional blocks for the remainder.

        k_new/v_new: (L, 1, T, KV, hd) contiguous.  Mutates ``seg`` in place
        (blocks list + n_tokens) and returns it.  Raises ``OutOfBlocks``
        (leaving ``seg`` unchanged) if the pool cannot hold the extension.
        """
        T = int(k_new.shape[2])
        if T == 0:
            return seg
        capacity = len(seg.blocks) * self.block_size
        need = (seg.n_tokens + T) - capacity
        if need > 0:
            seg.blocks.extend(self.pool.alloc(self.pool.blocks_for_tokens(need)))
        # slot coordinates for the appended token positions
        pos = np.arange(seg.n_tokens, seg.n_tokens + T)
        blk = np.asarray(seg.blocks, np.int64)[pos // self.block_size]
        slot = pos % self.block_size
        if self.device:
            k_new, v_new = self._place_segment(k_new, v_new)
        self.k = scatter_slots(self.k, blk, slot, k_new[:, 0])
        self.v = scatter_slots(self.v, blk, slot, v_new[:, 0])
        seg.n_tokens += T
        return seg

    def extend_alloc(self, seg: "PagedSegment",
                     n: int) -> Tuple[np.ndarray, np.ndarray]:
        """Reserve capacity for ``n`` more tokens WITHOUT writing data —
        the paged prefill step writes KV into the pool in place, so the
        store only needs to hand out the (block, slot) coordinates.

        Mutates ``seg`` (blocks list + n_tokens) and returns int32
        ``(blk, slot)`` arrays of shape (n,) for the new token positions.
        Raises ``OutOfBlocks`` (leaving ``seg`` unchanged — ``alloc`` checks
        capacity before mutating anything) if the pool cannot hold them.
        """
        capacity = len(seg.blocks) * self.block_size
        need = (seg.n_tokens + n) - capacity
        if need > 0:
            seg.blocks.extend(self.pool.alloc(self.pool.blocks_for_tokens(need)))
        pos = np.arange(seg.n_tokens, seg.n_tokens + n)
        blk = np.asarray(seg.blocks, np.int64)[pos // self.block_size]
        slot = pos % self.block_size
        seg.n_tokens += n
        return blk.astype(np.int32), slot.astype(np.int32)

    def gather(self, seg: "PagedSegment"):
        """Paged -> contiguous (L, 1, T, KV, hd)."""
        idx = (jnp.asarray(seg.blocks) if self.device
               else np.asarray(seg.blocks, np.int64))
        k = self.k[:, idx].swapaxes(2, 3)        # (L, nb, bs, KV, hd)
        v = self.v[:, idx].swapaxes(2, 3)
        L, nb, bs, KV, hd = k.shape
        k = k.reshape(L, nb * bs, KV, hd)[:, : seg.n_tokens]
        v = v.reshape(L, nb * bs, KV, hd)[:, : seg.n_tokens]
        return k[:, None], v[:, None]

    def free(self, seg: "PagedSegment") -> None:
        self.pool.decref(seg.blocks)

    def share(self, seg: "PagedSegment") -> None:
        """Refcount a segment's blocks for an additional reader (e.g. a
        running request's block table pointing at knowledge-tree blocks)."""
        self.pool.incref(seg.blocks)

    def share_blocks(self, blocks: Sequence[int]) -> None:
        """Refcount a raw block list — the counterpart of ``release``.
        Chunk-cache relocated reuse shares only the page-aligned TAIL of a
        node's segment, so the reader never holds a ``PagedSegment``."""
        self.pool.incref(blocks)

    def release(self, blocks: Sequence[int]) -> None:
        self.pool.decref(blocks)


@dataclasses.dataclass
class PagedSegment:
    store: PagedKVStore
    blocks: List[int]
    n_tokens: int

    @property
    def n_bytes(self) -> int:
        return len(self.blocks) * self.store.block_size * self.store.bytes_per_token()


# --------------------------------------------------------------------------
# disk tier: one mmap file per knowledge-tree node
# --------------------------------------------------------------------------

@dataclasses.dataclass
class DiskSegment:
    """Handle to one node's on-disk KV: a (2, L, 1, T, KV, hd) mmap file
    (k stacked over v).  Shape/dtype live in the handle — the file is raw."""
    store: "DiskSegmentStore"
    path: str
    shape: Tuple[int, ...]          # (L, 1, T, KV, hd)
    dtype: np.dtype
    n_bytes: int


class DiskSegmentStore:
    """mmap-file-per-segment disk tier.

    ``write`` creates the file and flushes it (np.memmap w+ mode), ``read``
    maps it read-only and materialises numpy copies, ``delete`` reclaims the
    file.  Byte accounting (``used_bytes``/``n_files``) is exact — the file
    size is 2 * T * kv_bytes_per_token, no block padding — so tests and
    metrics can assert reclamation."""

    def __init__(self, root_dir: str, capacity_bytes: int = 0):
        self.root = root_dir
        self.capacity_bytes = capacity_bytes
        self.used_bytes = 0
        self.n_files = 0
        self._count = itertools.count()
        os.makedirs(root_dir, exist_ok=True)

    def write(self, k: np.ndarray, v: np.ndarray) -> DiskSegment:
        """k/v: (L, 1, T, KV, hd) host arrays -> one mmap'd file."""
        k = np.asarray(k)
        v = np.asarray(v)
        path = os.path.join(self.root, f"seg{next(self._count):08d}.kv")
        mm = np.memmap(path, dtype=k.dtype, mode="w+", shape=(2,) + k.shape)
        mm[0] = k
        mm[1] = v
        mm.flush()
        n_bytes = int(mm.nbytes)
        del mm                          # drop the mapping, keep the file
        self.used_bytes += n_bytes
        self.n_files += 1
        return DiskSegment(self, path, tuple(k.shape), k.dtype, n_bytes)

    def read(self, seg: DiskSegment) -> Tuple[np.ndarray, np.ndarray]:
        mm = np.memmap(seg.path, dtype=seg.dtype, mode="r",
                       shape=(2,) + seg.shape)
        k, v = np.array(mm[0]), np.array(mm[1])
        del mm
        return k, v

    def delete(self, seg: DiskSegment) -> None:
        os.remove(seg.path)
        self.used_bytes -= seg.n_bytes
        self.n_files -= 1

    def clear(self) -> None:
        """Best-effort removal of every segment file (shutdown path)."""
        for name in os.listdir(self.root):
            if name.endswith(".kv"):
                try:
                    os.remove(os.path.join(self.root, name))
                except OSError:
                    pass
        self.used_bytes = 0
        self.n_files = 0

    def close(self) -> None:
        """clear() plus removal of the (then-empty) segment directory."""
        self.clear()
        try:
            os.rmdir(self.root)
        except OSError:
            pass


def make_disk_store(root_dir: Optional[str],
                    capacity_bytes: int) -> Optional[DiskSegmentStore]:
    """Build the disk tier for a serving engine: a fresh subdirectory under
    ``root_dir`` (or under the system temp dir when None), so two engines
    pointed at the same directory — e.g. serve.py --check-tokens running
    both engines — never collide on segment file names.  None = disabled."""
    if capacity_bytes <= 0:
        return None
    import atexit
    import tempfile
    if root_dir is not None:
        os.makedirs(root_dir, exist_ok=True)
    path = tempfile.mkdtemp(prefix="ragcache-disk-", dir=root_dir)
    store = DiskSegmentStore(path, capacity_bytes)
    # the engine owns no shutdown hook; reclaim the segment files (up to the
    # whole disk budget) and the directory when the process exits
    atexit.register(store.close)
    return store
