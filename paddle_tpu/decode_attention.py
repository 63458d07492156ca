"""The slot pool's KV-cache format: what a leaf is, how rows are
appended, how they are read.

**The leaf.**  One layer of every pooled decode step keeps ``k`` and
``v`` leaves ``[slots, T, n_kv_head * d_head]`` in their storage dtype
(fp32, bf16, int8): slot axis first, sequence axis :data:`KV_SEQ_AXIS`,
heads folded into the lane axis (``d_head`` = 64 alone would be padded
to a 128-lane tile in HBM and double the pool).  int8 leaves carry one
fp32 absmax scale per (slot, position, head) as sibling leaves
``k_scale``, ``v_scale`` ``[slots, T, n_kv_head]`` (``paddle_tpu.quant``).
:func:`kv_leaves` allocates a layer; nothing else in the tree spells the
layout out.  With the pool's recurrent leaves (no sequence axis: a
builder's own) the leaf kinds are four: SEQUENCE (above: position ``p``
in row ``p``, ``T`` the length rung), RING, LATENT, and recurrent.

**The latent leaf.**  A layer of latent attention keeps no ``k`` / ``v``
pair and no head axis: :func:`latent_leaves` allocates ONE ``latent``
leaf ``[slots, T, kv_lora_rank + rope]`` (the compressed row every head
shares: 512 + 64 lanes, zero-padded to whole 128-lane tiles: 640) and
ONE ``index_k`` leaf ``[slots, T, index_head_dim]`` (the key a learned
scorer reads), both sequence leaves
to the pool (position ``p`` in row ``p``: sliced, snapshotted and seated
like any other), appended in place by :func:`append_latent_rows`.  They
are read by SELECTION: the step scores a slot's index keys, names at
most ``k`` single positions a slot (the same for every head; a SET, in
ascending position order: ``latent_sparse_lm.select_positions`` finds it
by a threshold and a compaction, no sort of the rung —
``decode_attention_index_select_lowered_total{path}``), and
:func:`selected_latent_attention` attends to those rows of the latent
leaf and to nothing else, in the ABSORBED form (queries already
projected into the latent space; the context comes back in it) — the
leaf is never expanded to heads.  One lowering today, plain XLA ops (a
row gather told its list is sorted and unique, and a masked softmax:
``decode_attention_latent_lowered_total{path}``);
:func:`masked_latent_attention` is the contract whole over the rung, the
tests' parity reference.  A layer with no scorer (no ``index_k`` leaf)
reads EVERY live position for its ``K`` fresh rows through
:func:`dense_latent_attention`: on a TPU over bf16 leaves ONE Pallas
kernel a leaf that walks each slot's own live key blocks from a work
list (:func:`dense_latent_kernel_supported` is the rule), else the same
walk as XLA ops batched over slots.

**The ring leaf.**  A layer whose queries read only the last ``W``
positions (a sliding window that counts the query's own) keeps
``kv_leaves(..., window=W)``: the same leaves ``min(T, W)`` rows long,
position ``p`` in row ``p mod W``.  A one-token step appends there and
reads the rows ``< min(ts + 1, W)``: once a slot is past the window
every row of its ring is live and none of them is older than the window
(K was rotated at its own position and softmax does not care about
order, so the masked forms serve unchanged but for where they write).
``K`` fresh rows read the OLD ring and themselves before they overwrite
it (:func:`ring_positions` says which position each old row holds): on
a TPU through :func:`ring_rows_decode_attention`, a Pallas kernel that
takes a slot's ring as ONE block a leaf, where the heads are whole lane
tiles (:func:`ring_kernel_supported`), else as plain XLA ops.  A
wrapped row cannot be sliced by positions or rolled back, so the pool
carries a ring leaf whole (the builder declares its window:
``decoding.Leaf``) and refuses what would slice it.

**The contract.**  A step hands each slot ``n`` at position ``ts[n]``
``K >= 1`` fresh query / key / value rows (one decode step: ``K = 1``;
a speculative verify round: ``K`` rows).  Per step and layer

* **append in place** — row ``j`` of slot ``n`` lands at position
  ``ts[n] + j`` by an O(row) update of the donated leaf (a DMA of the
  row on the TPU, a scatter elsewhere; int8: quantized as it is
  written), never by re-emitting the leaf;
* **read what is live** — row ``j`` attends to positions ``<= ts[n] +
  j``; ``rep = n_head // n_kv_head`` query heads share a K/V head; a
  slot with ``ts[n] < 0`` (idle) is not written and gets a zero context
  row;
* or **read the blocks named for this row** — a step whose layer
  SELECTS what it reads (block-sparse attention) appends the same way
  (:func:`append_rows`) and hands :func:`grouped_block_decode_attention`
  per slot and K/V head a list of blocks: the slot attends to the live
  positions of those blocks and to nothing else, so its read does not
  grow with the rung.  The caller may DECLARE stretches of its lists
  that every head holds alike and that name consecutive blocks
  (``shared_runs``: a rule's forced window); the TPU kernel
  (:func:`block_sparse_decode_attention`) then reads each as ONE copy a
  leaf of whole-width rows and every other block as a head's own tile,
  one slot after another with the next reads in flight.

Three implementations of the read-what-is-live contract, chosen by
:func:`make_decode_attention` from what it can observe:

* :func:`ragged_decode_attention` — the Pallas TPU kernel, for fp32
  leaves, ``rep = 1``, ``K = 1``.  It reads RAGGED: slot ``n`` touches
  positions ``0..ts[n]`` rounded up to :data:`KV_TAIL` rows
  (:func:`kv_positions_read`), an idle slot nothing.  The step's live
  ``(slot, block)`` pairs are flattened into one work list
  (:func:`decode_work_items`, shared by every layer of a step) that
  carries each pair's rows: a slot's blocks before its last are whole
  ``block``-row items, its LAST block is read and computed only as far
  as it is live, in classes of :data:`KV_TAIL` rows (a DMA's length is
  static, so the kernel holds one body a class and an item takes the
  body of its rows).  The kernel walks the list with a dynamic trip
  count, the K/V reads of the next ``_READS_AHEAD`` items in flight
  across slot boundaries, online softmax over the rows read.  K/V stay
  fp32 in HBM; products are fp32 on the VPU, and the per-head sums ride
  the MXU as a ``[D, 128]`` 0/1 indicator matmul with the fp32 operand
  split three ways into bf16 (hi + mid + lo carries 24 mantissa bits;
  the indicator is exact), accumulated in fp32.
* :func:`grouped_decode_attention` — the Pallas TPU kernel for GROUPED
  heads (``rep > 1``) over unquantized sequence leaves, bf16 or fp32,
  whose heads a whole number of lane tiles holds (``Dh`` a multiple of
  128, or of 64 with an even number of K/V heads: two 64-lane heads a
  lane tile, ``lfm2``'s), ``K >= 1`` fresh rows a slot (``K`` is read
  from ``q``'s shape; at ``K = 1`` the program is the one-row kernel's)
  — and for ONE query head a K/V head (``rep = 1``) over bf16 leaves
  of whole-lane-tile heads at one fresh row (``olmo_hybrid``'s full
  layers).  The same ragged read from the
  same planner with its own sizes (:func:`step_read_sizes`: blocks of
  ``_GROUPED_BLOCK`` positions, fewer where a leaf's slab of them would
  pass ``_GROUPED_SLAB`` bytes, a slot's last one in
  ``_GROUPED_CLASSES`` classes), made for the slot's LAST fresh row
  (:func:`last_fresh_row`); the append left to :func:`append_rows`'
  in-place scatter before it.  An item is BOTH leaves' whole-width
  ``[rows, n_kv_head * Dh]`` slabs in one copy each, handed over as
  they lie (no ``[S, T, G, Dh]`` view), the next ``_GROUPED_AHEAD``
  items' reads in flight across slot boundaries; the K/V heads are
  scored from their own lanes of the slab by :func:`_block_part` — a
  head's ``K * rep`` query rows (fresh row major: the ``K`` rows of a
  slot lie BESIDE the query heads of their K/V head, padded to a sublane
  tile in VMEM only), each masked at its own position — products in the
  storage dtype, fp32 accumulation and online softmax.  How many heads
  one product scores is a parameter of the q layout (a head's lane
  offset and width) that follows from the shape (:func:`_unit_heads`).
  A head with ONE query row is one ROW of its unit (:func:`_head_rows`:
  the unit's heads consecutive rows, padded to a sublane tile once a
  unit and not once a head, each row's own ``Dh`` lanes filled by the
  kernel from a ``[heads, Dh]`` operand, the context written back a row
  a head): the same block-diagonal product at an eighth of the rows.
  Heads of 64 lanes are the same kernel at another parameter
  (:func:`_heads_a_tile`): nothing in the q layout needs ``Dh`` = 128,
  only a unit's lane slice and a context's output tile want whole lane
  tiles, so a unit holds an EVEN number of such heads and two heads'
  contexts leave the kernel side by side in one tile, split outside.
* :func:`grouped_masked_decode_attention` — the contract whole, as plain
  XLA ops (scatter append + masked softmax over the whole T axis):
  products in the storage dtype (int8: dequantized to fp32 at the read),
  fp32 accumulation and softmax.  The CPU path, the path of every step
  no kernel covers (int8 leaves, ring leaves at one fresh row or of
  heads narrower than a lane tile, ``K > 1`` over leaves of one query
  head a K/V head, and — at one row through
  :func:`lane_masked_decode_attention` on a TPU — what is left of heads
  narrower than a lane tile: grouped ones in an odd number or over a
  rung the block does not divide, and bf16 leaves of one query head a
  K/V head: the whole rung is read whatever of it is live;
  ``decode_attention_grouped_lowered_total{path}`` and
  ``decode_attention_ungrouped_lowered_total{path}`` count the form),
  and the parity reference of tests/test_decode_attention.py.

``jax.experimental.pallas`` is imported inside the kernel builder only:
``import paddle_tpu`` and the training cells never pay for it.
"""
from __future__ import annotations

import functools

import numpy as np

from paddle_tpu.monitor import registry as _registry

__all__ = ["KV_BLOCK", "KV_TAIL", "KV_SEQ_AXIS", "kv_leaves",
           "kv_read_block", "kv_positions_read", "decode_work_items",
           "step_read_sizes", "step_positions_read", "ragged_positions_read",
           "last_fresh_row",
           "ragged_decode_attention", "grouped_decode_attention",
           "grouped_masked_decode_attention",
           "lane_masked_decode_attention", "append_rows",
           "fresh_prompt_attention", "write_prompt_rows",
           "grouped_block_decode_attention", "block_sparse_decode_attention",
           "block_kernel_supported", "BLOCK_SPARSE_LOWERED",
           "kernel_supported", "make_decode_attention", "ring_positions",
           "ring_rows_decode_attention", "ring_kernel_supported",
           "RING_LOWERED", "GROUPED_LOWERED", "UNGROUPED_LOWERED",
           "latent_leaves", "append_latent_rows",
           "selected_latent_attention", "masked_latent_attention",
           "pad_lanes", "LATENT_LOWERED", "INDEX_SELECT_LOWERED",
           "dense_latent_attention", "dense_latent_positions_touched",
           "dense_latent_kernel_attention", "dense_latent_kernel_supported",
           "dense_latent_kernel_block",
           "dense_latent_positions_read", "dense_latent_work_items",
           "DENSE_LATENT_BLOCK", "divisor_block"]

BLOCK_SPARSE_LOWERED = _registry.REGISTRY.counter(
    "block_sparse_lowered_total",
    "reads of the blocks named for a row lowered (traced into a program "
    "or run eagerly), by the lowering chosen: kernel (Pallas TPU: the "
    "declared runs as whole-width slabs, a head's other blocks as tiles, "
    "the reads in flight by hand) | xla (a block gather and a masked "
    "softmax)", ("path",))

RING_LOWERED = _registry.REGISTRY.counter(
    "decode_attention_ring_lowered_total",
    "appends-and-reads over a RING leaf lowered (traced into a program "
    "or run eagerly), by the form: step (one fresh row a slot, written "
    "at its position modulo the window) | rows (K fresh rows that read "
    "the old ring and themselves before they overwrite it)", ("form",))

ROWS_LOWERED = _registry.REGISTRY.counter(
    "decode_attention_rows_lowered_total",
    "appends-and-reads of K > 1 fresh rows a slot lowered (a speculative "
    "round's verify, a drafting module's pass), by the kind of leaf they "
    "read: ring (the old ring's rows and the fresh ones, "
    "_ring_rows_attention) | sequence (what is live, through the grouped "
    "kernel, or the whole rung, masked, through the XLA form: "
    "decode_attention_grouped_lowered_total says which for grouped "
    "heads)", ("leaf",))
GROUPED_LOWERED = _registry.REGISTRY.counter(
    "decode_attention_grouped_lowered_total",
    "appends-and-reads of grouped heads (fewer K/V heads than query "
    "heads; one fresh row a slot or K) over SEQUENCE leaves lowered "
    "(traced into a program or run eagerly), by the lowering chosen: "
    "kernel (Pallas TPU: the live (slot, block) pairs as whole-width "
    "slabs, the reads in flight by hand) | xla (a masked softmax over "
    "the whole rung)", ("path",))

UNGROUPED_LOWERED = _registry.REGISTRY.counter(
    "decode_attention_ungrouped_lowered_total",
    "appends-and-reads of ONE query head per K/V head over SEQUENCE "
    "leaves lowered (traced into a program or run eagerly), by the "
    "lowering chosen: kernel (Pallas TPU, one fresh row a slot, what is "
    "live: ragged_decode_attention over fp32 leaves, "
    "grouped_decode_attention over bf16 leaves of whole-lane-tile heads) "
    "| xla (a masked softmax over the whole rung: int8 leaves, K rows, "
    "the CPU; bf16 leaves no kernel takes, on a TPU at one row through "
    "the form that reads them as they lie)",
    ("path",))

LATENT_LOWERED = _registry.REGISTRY.counter(
    "decode_attention_latent_lowered_total",
    "reads of the positions named for a row over a LATENT leaf lowered "
    "(traced into a program or run eagerly), by the lowering chosen: xla "
    "(a gather of the named rows and a masked softmax over them, "
    "absorbed: the leaf is never expanded to heads); and DENSE reads of "
    "every live position for K fresh rows (dense_latent_attention): "
    "dense_kernel (Pallas TPU: each slot's OWN live key blocks from a "
    "work list, the leaf read as it lies, the running softmax in VMEM) | "
    "dense_xla (the rung walked in key blocks under one running softmax, "
    "up to the pool's longest live context, for every slot: the CPU, "
    "float32 leaves, a rung the block does not divide)", ("path",))

INDEX_SELECT_LOWERED = _registry.REGISTRY.counter(
    "decode_attention_index_select_lowered_total",
    "selections of the positions a row reads over a latent layer's index "
    "scores lowered (latent_sparse_lm.select_positions, traced into a "
    "program or run eagerly), by the lowering chosen: threshold (the "
    "k-th largest score found by counting, ties the lowest position "
    "first, the chosen listed in ascending position order by "
    "compaction: no sort of the rung)", ("path",))

#: the sequence axis of every K/V leaf (and scale sibling)
KV_SEQ_AXIS = 1

#: positions per K/V block the kernel moves in one DMA
KV_BLOCK = 128
#: rows a slot's LAST block is read and computed in: the rounding of what
#: a step reads (:func:`kv_positions_read`).  64 of 16 / 32 / 64 on the
#: chip: an item's time is mostly fixed (0.21 us of ~1.1 for 32 rows), so
#: finer classes buy less than their branches and bodies cost
KV_TAIL = 64
#: items whose K/V reads are in flight ahead of the one computed: a short
#: tail cannot hide the next whole block's read behind its own products,
#: two items can
_READS_AHEAD = 2
_HEAD_LANES = 128   # heads padded to one lane tile in the score domain
#: tile reads the block kernel issues a turn of its loop (2 to 32 read
#: alike on the chip, one a turn 4% slower: the copies set the pace)
_TILE_UNROLL = 4
#: keys of a unit the block kernel scores at a time (whole blocks): the
#: kernel's body holds ONE ``[rep, _SCORE_ROWS]`` chain of products and
#: softmax a unit kind, looped over the unit.  A chunk costs ~0.5 us
#: whatever it holds (256 / 512 / 1024 / 4096 keys: 1.50 / 0.82 / 0.53 /
#: 0.27 ms a call of arithmetic at the cell's shapes, where the copies
#: take 0.60: chip runs, PR 42), so a unit of the cell (2,176 and 4,096
#: keys) is one chunk; longer lists loop
_SCORE_ROWS = 4096
#: positions of a (slot, block) pair the grouped kernel moves in one DMA a
#: leaf (a rung shorter than it is one block).  256 / 512 / 1024 / 2048
#: read alike at ``[40,16384,512]`` (1.24 ms a call: the copies set the
#: pace, 705 GB/s); at ``[80,1024,512]``, where a slot is one or two
#: items, 512 reads 0.160-0.168 ms for 1024's 0.183 (finer rounding) and
#: 256's 0.182 (more items): chip runs, PR 44, tools/time_grouped_decode.py
_GROUPED_BLOCK = 512
#: bytes one leaf's slab of a block may hold; a wider leaf's block is
#: halved until it does (:func:`step_read_sizes`).  Where a slab is
#: megabytes an item's fixed cost no longer shows and the finer rounding
#: of a shorter block wins: ``[80,1024,3840]`` bf16 (30 one-row heads,
#: contexts ~330) reads 0.638 ms a call in blocks of 256 (2.0 MB a slab,
#: 1.055 of the live bytes) for 0.669 in blocks of 512 (3.9 MB, 1.109),
#: whatever the heads a unit (chip run, PR 53, tools/time_grouped_decode.py)
_GROUPED_SLAB = 2 << 20
#: classes a slot's last block is read in (its ``tail`` is the block over
#: them, 64 rows: the rounding of what a step of the grouped kernel
#: reads, 1.003 of the live positions at 10.7k contexts, 1.10 at ~330);
#: 4 and 8 read alike
_GROUPED_CLASSES = 8
#: items whose reads are in flight ahead of the one the grouped kernel
#: scores (2 / 3 / 5 read alike)
_GROUPED_AHEAD = 2
#: K/V heads the grouped kernel scores in one product (0: by the rule of
#: :func:`_unit_heads`; a number: the tool's experiments).
#: A block's chain of products and softmax costs ~0.5 us whatever it
#: holds, so four heads in ONE block-diagonal product (the MXU's work is
#: the same: a head's 8 rows or the unit's 32 fill a fraction of its
#: columns) is 0.62 ms a call of arithmetic at ``[40,16384,512]`` where a
#: head a product is 1.65 — over the copies' 1.24, which it then sets back
#: to 1.82
_GROUPED_HEADS = 0
#: query rows a unit of the grouped kernel may hold (a head's ``K * rep``
#: rows in whole sublane tiles, times the heads of the unit): a unit's
#: product multiplies ``heads - 1`` zeros for every number, so past this
#: the MXU's time passes the copies'.  One-row steps of four heads are
#: 32 rows, one unit (``[40,16384,512]`` rep 7, ``[80,1024,512]`` rep 5:
#: the timings above).  ``[128,4096,1024]`` rep 8 at ``K = 2`` (16 rows a
#: head, 8 heads), ms a call with / without the copies' / without the
#: arithmetic's part — 8 heads a unit (128 rows x 1024 lanes) 0.897 /
#: 0.789 / 0.882; **4 (64 x 512) 0.842 / 0.654 / 0.834**; 2 0.908 / 0.838
#: / 0.828; 1 1.301 / 1.241 / 0.812: at four heads the arithmetic hides
#: behind the copies (545 GB/s on what is live, slots of one to three
#: items), at eight the zeros cost as much as the copies, at two and one
#: the chains do (chip run, PR 48, tools/time_grouped_decode.py; the XLA
#: form of the same read: 5.07)
_GROUPED_UNIT_ROWS = 64
#: VMEM a kernel may use before it has to ask for more (v5e's compiler)
_VMEM_DEFAULT = 16 << 20
_MASK = -1e30       # finite: exp(_MASK - m) == 0, no inf - inf


def kv_read_block(seq_len: int) -> int:
    """The block (in positions) a decode step reads a length-``seq_len``
    rung in: :data:`KV_BLOCK` when it divides the rung, else the rung.
    A slot's blocks before its last are read whole; its last one as far
    as :func:`kv_positions_read` says."""
    seq_len = int(seq_len)
    return KV_BLOCK if seq_len % KV_BLOCK == 0 else seq_len


def kv_positions_read(ts, block: int, tail=None):
    """Positions of a slot a kernel reads in a step at ``ts >= 0``
    (``ts``: an int or an integer array, numpy or jax), in blocks of
    ``block`` whose last is read in classes of ``tail`` rows: ``ts + 1``
    rounded up to ``tail``.  ``tail`` unsaid is the ragged kernel's:
    :data:`KV_TAIL`, or the block where :data:`KV_TAIL` does not divide
    it; the grouped kernel's comes with its block from
    :func:`step_read_sizes`.  THE rounding: the work list
    (:func:`decode_work_items`) and the server's
    ``serving_decode_kv_positions_read_total`` both take it from here."""
    if tail is None:
        tail = KV_TAIL if block % KV_TAIL == 0 else block
    return (ts // tail + 1) * tail


def kernel_supported(seq_len: int, d_model: int, n_head: int) -> bool:
    """Shapes the TPU kernel lowers for: lane-dense rows (``d_model`` a
    multiple of 128), sublane-aligned blocks, heads within one lane tile."""
    return (d_model % 128 == 0 and kv_read_block(seq_len) % 8 == 0
            and n_head <= _HEAD_LANES)


def _heads_a_tile(d_head: int) -> int:
    """Consecutive K/V heads of ``d_head`` lanes whose lanes together are
    whole lane tiles, which is what the grouped kernel slices a slab by
    and writes a context out in: 1 where a head is whole tiles itself, 2
    where it is an odd number of half tiles (64 lanes: two heads a tile),
    0 where no such pair is (no kernel)."""
    half = _HEAD_LANES // 2
    return 0 if d_head % half else 1 + d_head // half % 2


def step_read_sizes(seq_len: int, width: int, dtype, *, n_head: int,
                    n_kv_head: int, backend=None):
    """``(block, tail)`` the grouped kernel reads a step's (one fresh
    row a slot or ``K``) unquantized sequence leaves ``[S, seq_len,
    width]`` of ``dtype`` in
    (a slot's blocks before its last whole, its last in classes of
    ``tail`` rows: :func:`kv_positions_read`), or None where that step
    is not the kernel's: the backend (``jax.default_backend()`` unsaid)
    no TPU, heads that no whole number of :func:`_heads_a_tile` holds
    (a head's lanes no multiple of 64, or an odd number of K/V heads of
    an odd number of half tiles), a dtype other than bf16 and fp32, a
    rung its block does not divide, or ONE query head a K/V head over
    leaves that are not bf16 (fp32 ones are
    :func:`ragged_decode_attention`'s: bit-exact fp32 products) or whose
    heads are not whole lane tiles themselves."""
    import jax
    import jax.numpy as jnp

    pair = (_heads_a_tile(width // n_kv_head)
            if 0 < n_kv_head <= n_head and width % n_kv_head == 0 else 0)
    block = _GROUPED_BLOCK
    while (block * width * jnp.dtype(dtype).itemsize > _GROUPED_SLAB
           and block > 16 * _GROUPED_CLASSES):
        block //= 2
    block = min(int(seq_len), block)
    tail = block // _GROUPED_CLASSES
    if ((backend or jax.default_backend()) != "tpu"
            or not pair or n_head % n_kv_head or n_kv_head % pair
            or jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32)
            or (n_kv_head == n_head
                and (jnp.dtype(dtype) != jnp.bfloat16 or pair > 1))
            or seq_len % block or block % _GROUPED_CLASSES or tail % 16):
        return None
    return block, tail


def step_positions_read(ts, seq_len: int, **leaves):
    """Positions a step whose (last) fresh row is at ``ts >= 0`` reads
    of a slot's sequence leaves (``leaves``: what :func:`step_read_sizes`
    takes after the rung): the grouped kernel's rounding where it serves
    them, else the whole rung (an XLA form).  What a builder of grouped
    heads, or of one query head a K/V head over bf16 leaves, declares as
    its ``"kv"`` read (``decoding.PositionRead``) for the server's
    counter (a ``K``-row round: at :func:`last_fresh_row`)."""
    sizes = step_read_sizes(seq_len, **leaves)
    if sizes is None:
        return np.full_like(ts, seq_len)
    return kv_positions_read(ts, *sizes)


def ragged_positions_read(ts, seq_len: int):
    """Positions a one-row step at ``ts >= 0`` reads of a slot's fp32
    leaves where :func:`ragged_decode_attention` serves it: blocks of
    :func:`kv_read_block`, the last in classes of :data:`KV_TAIL` rows.
    Declared as the ``"kv"`` read over such leaves WHATEVER the backend
    (the counter has said so since PR 37; tier-1 holds it on a CPU)."""
    return kv_positions_read(ts, kv_read_block(seq_len))


def decode_work_items(ts, seq_len: int, block: int, tail=None):
    """Flatten the step's live ``(slot, block)`` pairs, slot-major.

    ``ts`` [S] int32 (``< 0`` = idle).  Returns ``(n_items [1], slot
    [S * seq_len // block], blk [same], rows [same])`` int32; entries
    past ``n_items`` are padding.  A slot at position ``ts`` owns blocks
    ``0..ts // block`` — at least the one its new row lands in — and
    ``rows`` is how many of a block's rows the item reads: the whole
    block, or for the slot's last block what is left of
    :func:`kv_positions_read` (in classes of ``tail`` rows, as there)."""
    import jax.numpy as jnp

    S = ts.shape[0]
    max_items = S * (seq_len // block)
    nblk = jnp.where(ts >= 0, ts // block + 1, 0).astype(jnp.int32)
    ends = jnp.cumsum(nblk)
    slot = jnp.repeat(jnp.arange(S, dtype=jnp.int32), nblk,
                      total_repeat_length=max_items)
    blk = jnp.arange(max_items, dtype=jnp.int32) - (ends - nblk)[slot]
    rows = jnp.minimum(
        kv_positions_read(ts[slot], block, tail) - blk * block, block)
    return (ends[-1:].astype(jnp.int32), slot, blk.astype(jnp.int32),
            rows.astype(jnp.int32))


@functools.lru_cache(maxsize=None)
def _indicators(d_model: int, n_head: int):
    """``E`` [D, 128] with ``E[d, h] = 1`` where lane ``d`` belongs to
    head ``h`` (a lane-to-head sum as a matmul), and its transpose (a
    head-to-lanes broadcast)."""
    e = (np.arange(d_model)[:, None] // (d_model // n_head)
         == np.arange(_HEAD_LANES)[None, :]).astype(np.float32)
    return e, np.ascontiguousarray(e.T)


def _split3(x):
    """fp32 -> three bf16 terms whose sum carries x to ~2^-24."""
    import jax.numpy as jnp

    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _dot3(x, w):
    """``x @ w`` for fp32 ``x`` and an exactly-bf16 0/1 ``w``: three
    single-pass MXU matmuls, fp32 accumulation."""
    import jax.numpy as jnp

    return sum(jnp.dot(t, w, preferred_element_type=jnp.float32)
               for t in _split3(x))


def _by_rows(rows, classes, body):
    """``body(c)`` for the class ``c`` of ``classes`` (static, rising)
    that ``rows`` (traced) equals, found by halving (a ``lax.switch``
    lowers to a cascade that costs every item a branch a class)."""
    import jax

    def pick(cs):
        if len(cs) == 1:
            return functools.partial(body, cs[0])
        lo, hi = cs[:len(cs) // 2], cs[len(cs) // 2:]
        return lambda: jax.lax.cond(rows >= hi[0], pick(hi), pick(lo))

    pick(classes)()


def _kernel(n_items_ref, item_slot_ref, item_blk_ref, item_rows_ref,
            ts_ref,                                             # SMEM
            q_ref, kn_ref, vn_ref, e_ref, et_ref,               # VMEM
            k_hbm, v_hbm,                                       # HBM (ANY)
            o_ref, k_out, v_out,                                # outputs
            kbuf, vbuf, m_ref, l_ref, acc_ref, rsem, wsem, *, block):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_items = n_items_ref[0]
    o_ref[...] = jnp.zeros_like(o_ref)   # idle slots: zero context
    # the row counts an item may have (decode_work_items): one static
    # body each, since a DMA's length is static
    tail = kv_positions_read(0, block)
    classes = list(range(tail, block + 1, tail))

    def by_rows(i, body):
        _by_rows(item_rows_ref[i], classes, body)

    def read(i, buf, rows):
        n, b = item_slot_ref[i], item_blk_ref[i]
        src = pl.ds(pl.multiple_of(b * block, block), rows)
        dst = pl.ds(0, rows)
        return (pltpu.make_async_copy(k_hbm.at[n, src], kbuf.at[buf, dst],
                                      rsem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[n, src], vbuf.at[buf, dst],
                                      rsem.at[1, buf]))

    def start_read(i, buf):
        def start(rows):
            for c in read(i, buf, rows):
                c.start()
        by_rows(i, start)

    nbuf = kbuf.shape[0]            # _READS_AHEAD + 1 buffers a leaf
    for j in range(nbuf - 1):
        pl.when(n_items > j)(functools.partial(start_read, j, j))

    def attend(i, rows):
        """Item ``i`` over the first ``rows`` rows of its block."""
        buf = i % nbuf
        n, b = item_slot_ref[i], item_blk_ref[i]
        t = ts_ref[n]
        last = b == t // block

        # the wait takes the descriptor the read was started with
        for c in read(i, buf, rows):
            c.wait()

        @pl.when(b == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, _MASK)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        row = pl.ds(n, 1)
        # the sublane tile of 8 positions the new row falls in: a DMA
        # moves whole tiles, so the append writes the patched tile back
        # (its other 7 rows as they were read); it lies inside the rows
        # read, which reach past ``t``
        t8 = pl.multiple_of(t // 8 * 8, 8)
        tile = pl.ds(pl.multiple_of(t8 - b * block, 8), 8)
        writes = (pltpu.make_async_copy(kbuf.at[buf, tile],
                                        k_out.at[n, pl.ds(t8, 8)],
                                        wsem.at[0]),
                  pltpu.make_async_copy(vbuf.at[buf, tile],
                                        v_out.at[n, pl.ds(t8, 8)],
                                        wsem.at[1]))

        @pl.when(last)
        def _():
            # append: the row is patched into the block just read (so the
            # read never waits on it) and goes to HBM by its own DMA
            r = pl.ds(t - b * block, 1)
            kbuf[buf, r, :] = kn_ref[row, :]
            vbuf[buf, r, :] = vn_ref[row, :]
            for c in writes:
                c.start()

        live = pl.ds(0, rows)
        s = _dot3(kbuf[buf, live] * q_ref[row, :], e_ref[...])  # [rows, 128]
        pos = b * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(pos <= t, s, _MASK)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        m_ref[...] = m_new
        alpha = jnp.exp(m_prev - m_new)                     # [1, 128]
        p = jnp.exp(s - m_new)
        # one expansion matmul for p and alpha: heads -> their lanes
        x = _dot3(jnp.concatenate(
            [p, jnp.broadcast_to(alpha, (8, alpha.shape[1]))], axis=0),
            et_ref[...])                                    # [rows+8, D]
        pe, ae = x[:rows], x[rows:rows + 1]
        l_ref[...] = ae * l_ref[...] + jnp.sum(pe, axis=0, keepdims=True)
        acc_ref[...] = ae * acc_ref[...] + jnp.sum(
            pe * vbuf[buf, live], axis=0, keepdims=True)

        @pl.when(last)
        def _():
            o_ref[row, :] = acc_ref[...] / l_ref[...]
            for c in writes:
                c.wait()

    def item(i, carry):
        @pl.when(i + nbuf - 1 < n_items)
        def _():
            start_read(i + nbuf - 1, (i + nbuf - 1) % nbuf)

        by_rows(i, functools.partial(attend, i))
        return carry

    jax.lax.fori_loop(0, n_items, item, 0)


def ragged_decode_attention(q, k_new, v_new, k_cache, v_cache, ts, work,
                            *, n_head: int, scale: float, block: int,
                            interpret=False):
    """The Pallas TPU kernel (see the module docstring).

    ``q``, ``k_new``, ``v_new`` [S, D] fp32 (scores are ``scale * q.k``
    per head); ``k_cache``, ``v_cache`` [S, T, D] fp32, updated in
    place (aliased to the returned leaves); ``ts`` [S] int32; ``work``
    from :func:`decode_work_items` for the same ``ts``/``block``.
    Returns ``(ctx [S, D], k_cache, v_cache)``."""
    import jax.numpy as jnp

    # the indicators stay the CALLER's constants (a pool hoists and
    # places them once: KVSlotPool._lower), the call itself is one jit
    e, et = _indicators(k_cache.shape[-1], n_head)
    return _kernel_call()(
        work, ts, q * scale, k_new, v_new, jnp.asarray(e, jnp.bfloat16),
        jnp.asarray(et, jnp.bfloat16), k_cache, v_cache, block=block,
        interpret=interpret)


@functools.lru_cache(maxsize=None)
def _kernel_call():
    """:func:`_call` under ``jax.jit`` (built once, jax imported late):
    the layers and steps of a chunk program then share ONE trace and ONE
    lowered function of the kernel instead of tracing and lowering its
    bodies at every call site in every process (a cache hit on the
    executable does not spare the lowering; fused_attention._jitted)."""
    import jax

    return jax.jit(_call, static_argnames=("block", "interpret"))


def _call(work, ts, q, k_new, v_new, e, et, k_cache, v_cache, *, block,
          interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, T, D = k_cache.shape
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    f32 = jnp.float32
    nbuf = _READS_AHEAD + 1
    # fp32 words the kernel keeps in VMEM: q, k_new, v_new and the
    # context whole, the K and V blocks being read and computed, and room
    # for the block-sized temporaries of the products, their splits and
    # sums
    resident = 4 * (4 * S * D + 2 * nbuf * block * D + 8 * (block + 8) * D)
    return pl.pallas_call(
        functools.partial(_kernel, block=block),
        out_shape=(jax.ShapeDtypeStruct((S, D), f32),
                   jax.ShapeDtypeStruct(k_cache.shape, f32),
                   jax.ShapeDtypeStruct(v_cache.shape, f32)),
        in_specs=[smem] * 5 + [vmem] * 5 + [hbm] * 2,
        out_specs=(vmem, hbm, hbm),
        scratch_shapes=[
            pltpu.VMEM((nbuf, block, D), f32),
            pltpu.VMEM((nbuf, block, D), f32),
            pltpu.VMEM((1, _HEAD_LANES), f32),
            pltpu.VMEM((1, D), f32),
            pltpu.VMEM((1, D), f32),
            pltpu.SemaphoreType.DMA((2, nbuf)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        input_output_aliases={10: 1, 11: 2},  # k_cache, v_cache in place
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=min(100 << 20, max(32 << 20, 2 * resident))),
        name="ragged_decode_attention",
        interpret=interpret,
    )(*work, ts, q, k_new, v_new, e, et, k_cache, v_cache)


def kv_leaves(n_rows: int, seq_len: int, n_kv_head: int, d_head: int,
              dtype, window=None):
    """One layer's zeroed K/V leaves in the pool's format (the module
    docstring): ``k``, ``v`` ``[n_rows, seq_len, n_kv_head * d_head]`` in
    ``dtype``; for int8 also ``k_scale``, ``v_scale`` ``[n_rows, seq_len,
    n_kv_head]`` fp32.  Every leaf's sequence axis is
    :data:`KV_SEQ_AXIS`.  ``window``: a RING leaf, ``min(seq_len,
    window)`` rows long, position ``p`` in row ``p mod window``."""
    import jax.numpy as jnp

    if window is not None:
        seq_len = min(int(seq_len), int(window))
    rows = (n_rows, seq_len, n_kv_head * d_head)
    leaves = {"k": jnp.zeros(rows, dtype), "v": jnp.zeros(rows, dtype)}
    if jnp.dtype(dtype) == jnp.int8:
        scales = (n_rows, seq_len, n_kv_head)
        leaves.update(k_scale=jnp.zeros(scales, jnp.float32),
                      v_scale=jnp.zeros(scales, jnp.float32))
    return leaves


def _append(kv, name, new, rows, at, heads):
    """Leaf ``name`` of ``kv`` (and its scale sibling) with the rows
    ``new`` [S, ..., Dkv] written in place at ``[rows, at]``; a position
    ``>= T`` is dropped."""
    if name + "_scale" not in kv:
        return {name: kv[name].at[rows, at].set(
            new.astype(kv[name].dtype), mode="drop")}
    from paddle_tpu.quant import quantize_rows

    # quantize-on-write: one absmax scale per fresh (row, head)
    codes, scales = quantize_rows(new.reshape(new.shape[:-1] + heads))
    return {name: kv[name].at[rows, at].set(codes.reshape(new.shape),
                                            mode="drop"),
            name + "_scale": kv[name + "_scale"].at[rows, at].set(
                scales, mode="drop")}


def _read(kv, name, heads):
    """Leaf ``name`` as the products see it: ``[S, T, n_kv_head,
    d_head]``, int8 codes dequantized to fp32 (int8 bytes leave HBM)."""
    leaf = kv[name].reshape(kv[name].shape[:2] + heads)
    if name + "_scale" not in kv:
        return leaf
    from paddle_tpu.quant import dequantize_rows

    return dequantize_rows(leaf, kv[name + "_scale"])


def ring_positions(ts, rows: int):
    """The position each row of a ring leaf of ``rows`` rows holds
    BEFORE a step at ``ts`` (``[...]`` int32) writes: ``[..., rows]``,
    the largest ``p < ts`` with ``p mod rows == r``; negative where the
    row holds nothing of this sequence yet."""
    import jax.numpy as jnp

    last = ts[..., None] - 1
    return last - (last - jnp.arange(rows, dtype=ts.dtype)) % rows


def _beside_heads(x, n_kv_head: int, rep: int):
    """``x`` ``[S, K, n_kv_head * rep * Dh]`` (``K`` rows a slot) as
    ``[S, n_kv_head, K * rep, Dh]``: the rows of a slot beside the
    ``rep`` query heads of their K/V head — the ONE layout of both K-row
    forms, in which their products have the one-row form's shape and
    read the leaves AS THEY LIE."""
    import jax.numpy as jnp

    S, K, width = x.shape
    x = x.reshape(S, K, n_kv_head, rep, width // (n_kv_head * rep))
    return jnp.swapaxes(x, 1, 2).reshape(S, n_kv_head, K * rep, -1)


def _mask_beside_heads(ok, n_kv_head: int, rep: int):
    """A ``[S, K, T]`` mask for scores ``[S, n_kv_head, K * rep, T]``."""
    import jax.numpy as jnp

    S, K, T = ok.shape
    return jnp.broadcast_to(ok[:, None, :, None, :],
                            (S, n_kv_head, K, rep, T)).reshape(
                                S, n_kv_head, K * rep, T)


def _rows_apart(ctx, K: int):
    """:func:`_beside_heads` undone: a context ``[S, n_kv_head, K * rep,
    Dh]`` as ``[S, K, n_head * Dh]``."""
    import jax.numpy as jnp

    S, g, R, Dh = ctx.shape
    return jnp.swapaxes(ctx.reshape(S, g, K, R // K, Dh), 1, 2).reshape(
        S, K, R // K * g * Dh)


def _ring_rows_attention(q, k_new, v_new, kv, ts, *, n_head, n_kv_head,
                         scale, window, read=None):
    """``K`` fresh rows a slot over RING leaves: row ``j`` (position
    ``ts + j``) reads the old ring's rows that hold a position inside
    its window and the fresh rows ``<= j`` inside it; then the fresh
    rows are written at their positions modulo the ring (of more than
    ``rows`` fresh rows the last ``rows`` stay).  Unquantized leaves
    only.

    The layout is :func:`_grouped_rows_attention`'s
    (:func:`_beside_heads`).  The OLD ring is scored AS IT LIES and the
    ``K`` fresh rows apart, under ONE float32 softmax (shared maximum
    and sum; the weights rounded to the storage dtype after the joint
    normalisation), and the two context products are added: no run of
    ``rows + K`` keys is ever built.  Scored as one such run with the
    rows on an axis of their own, every window layer of a round copied
    both ring leaves, built the run, re-laid it by heads and took its
    softmax over two lane tiles for 130 keys (seen in the compiled round
    of ``k_exaone_236b_a23b``: 3.0 ms of a 21.6 ms round for four rings
    that stream in 0.33, PR 59).

    ``read(qg, fresh_k, fresh_v, kv, old_ok, new_ok) -> ctx [S,
    n_kv_head, K * rep, Dh]``: :func:`_ring_rows_read` (plain XLA ops;
    None) or the kernel's (:func:`ring_rows_decode_attention`)."""
    import jax.numpy as jnp

    if "k_scale" in kv:
        raise ValueError("K fresh rows over int8 ring leaves are not "
                         "supported")
    S, L, Dkv = kv["k"].shape
    K = q.shape[1]
    heads = (n_kv_head, Dkv // n_kv_head)
    rep, dt = n_head // n_kv_head, kv["k"].dtype
    live = ts >= 0
    pos = ts[:, None] + jnp.arange(K)[None, :]                  # [S, K]
    held = ring_positions(jnp.maximum(ts, 0), L)                # [S, L]
    old_ok = ((held >= 0)[:, None, :]
              & (pos[:, :, None] - held[:, None, :] < window))
    new_ok = ((pos[:, None, :] <= pos[:, :, None])
              & (pos[:, :, None] - pos[:, None, :] < window))
    qg = _beside_heads((q * scale).astype(dt), n_kv_head, rep)
    # the fresh rows as the leaf will hold them
    fresh_k, fresh_v = (x.astype(dt).reshape((S, K) + heads)
                        for x in (k_new, v_new))
    ctx = (read or _ring_rows_read)(qg, fresh_k, fresh_v, kv, old_ok, new_ok)
    rows = jnp.arange(S)[:, None]
    keep = live[:, None] & (jnp.arange(K)[None, :] >= K - L)
    at = jnp.where(keep, pos % L, L)            # dropped: out of range
    kv = {**_append(kv, "k", k_new, rows, at, heads),
          **_append(kv, "v", v_new, rows, at, heads)}
    return jnp.where(live[:, None, None], _rows_apart(ctx, K), 0.0), kv


def _ring_rows_read(qg, fresh_k, fresh_v, kv, old_ok, new_ok):
    """The read of :func:`_ring_rows_attention` as plain XLA ops: the
    one-row form's product over the leaf viewed ``[S, rows, n_kv_head,
    Dh]`` and the fresh rows' beside it.  The CPU's form and the
    kernel's parity reference."""
    import jax.numpy as jnp

    S, g, R, _ = qg.shape
    heads, dt = fresh_k.shape[2:], fresh_k.dtype
    rep = R // fresh_k.shape[1]
    s_old = jnp.where(_mask_beside_heads(old_ok, g, rep), jnp.einsum(
        "sgrd,stgd->sgrt", qg, _read(kv, "k", heads),
        preferred_element_type=jnp.float32), -1e9)
    s_new = jnp.where(_mask_beside_heads(new_ok, g, rep), jnp.einsum(
        "sgrd,skgd->sgrk", qg, fresh_k,
        preferred_element_type=jnp.float32), -1e9)
    top = jnp.maximum(s_old.max(-1, keepdims=True),
                      s_new.max(-1, keepdims=True))
    e_old, e_new = jnp.exp(s_old - top), jnp.exp(s_new - top)
    total = e_old.sum(-1, keepdims=True) + e_new.sum(-1, keepdims=True)
    return (jnp.einsum("sgrt,stgd->sgrd", (e_old / total).astype(dt),
                       _read(kv, "v", heads),
                       preferred_element_type=jnp.float32)
            + jnp.einsum("sgrk,skgd->sgrd", (e_new / total).astype(dt),
                         fresh_v, preferred_element_type=jnp.float32))


def _grouped_rows_attention(q, k_new, v_new, kv, ts, *, n_head, n_kv_head,
                            scale):
    """``K`` fresh rows a slot of GROUPED heads over sequence leaves:
    the contract of :func:`grouped_masked_decode_attention`, with the
    ``K`` rows of a slot laid beside the ``rep`` query heads of their
    K/V head (``[S, n_kv_head, K * rep, Dh]``: :func:`_beside_heads`),
    so that both products have the one-row form's shape and read the
    leaves AS THEY LIE.  With
    the rows on an axis of their own the compiler re-lays the leaves out
    instead — a copy of the whole rung, K and V, every layer and round
    (seen in the compiled round of ``k_exaone_236b_a23b``: four 1.07 GB
    copies and 2.4 GB of temporaries a call where this form has 0.54
    GB, PR 47).  :func:`_ring_rows_attention` lays its rows out the same
    way since PR 59: the two K-row forms share ONE layout."""
    import jax
    import jax.numpy as jnp

    S, T, Dkv = kv["k"].shape
    K = q.shape[1]
    heads = (n_kv_head, Dkv // n_kv_head)
    rep = n_head // n_kv_head
    dt = jnp.float32 if "k_scale" in kv else kv["k"].dtype
    live = ts >= 0
    pos = ts[:, None] + jnp.arange(K)[None, :]
    at = jnp.where(live[:, None], pos, T)   # idle -> out of range, dropped
    rows = jnp.arange(S)[:, None]
    kv = {**_append(kv, "k", k_new, rows, at, heads),
          **_append(kv, "v", v_new, rows, at, heads)}
    ok = _mask_beside_heads(
        jnp.arange(T)[None, None, :] <= pos[..., None], n_kv_head, rep)
    qg = _beside_heads((q * scale).astype(dt), n_kv_head, rep)
    scores = jnp.einsum("sgrd,stgd->sgrt", qg, _read(kv, "k", heads),
                        preferred_element_type=jnp.float32)
    w = jax.nn.softmax(jnp.where(ok, scores, -1e9), axis=-1)
    ctx = jnp.einsum("sgrt,stgd->sgrd", w.astype(dt), _read(kv, "v", heads),
                     preferred_element_type=jnp.float32)
    return jnp.where(live[:, None, None], _rows_apart(ctx, K), 0.0), kv


def grouped_masked_decode_attention(q, k_new, v_new, kv, ts,
                                    *, n_head: int, n_kv_head: int,
                                    scale: float, window=None):
    """The contract as plain XLA ops, for every leaf dtype, head grouping
    and number of fresh rows.

    ``kv``: one layer's leaves (:func:`kv_leaves`); ``ts`` [S] int32.
    ``q`` [S, n_head * Dh], ``k_new``, ``v_new`` [S, n_kv_head * Dh] fp32
    for one fresh row per slot at ``ts``, or ``[S, K, ...]`` for ``K``
    rows at ``ts .. ts + K - 1``, row ``j`` reading positions ``<= ts +
    j`` (the rows before it among them).  The new rows are rounded to
    the storage dtype as they are appended (int8: quantized per row and
    head); scores and the context are products in the storage dtype
    (int8: fp32, dequantized at the read) accumulated in fp32; the
    softmax is fp32 over the whole T axis, masked.  Returns ``(ctx``
    shaped like ``q``, fp32, ``kv)``.

    ``window`` (the leaves are RING leaves, ``kv_leaves(...,
    window=window)``): one fresh row is written at ``ts`` modulo the
    ring's rows and reads the rows ``<= ts``, which past the window is
    all of them; ``K`` rows go through :func:`_ring_rows_attention`."""
    import jax
    import jax.numpy as jnp

    if q.ndim == 3:
        ROWS_LOWERED.labels(
            leaf="sequence" if window is None else "ring").inc()
    if window is not None:
        RING_LOWERED.labels(form="step" if q.ndim == 2 else "rows").inc()
        if q.ndim == 3:
            return _ring_rows_attention(
                q, k_new, v_new, kv, ts, n_head=n_head, n_kv_head=n_kv_head,
                scale=scale, window=int(window))
    elif q.ndim == 3 and n_kv_head < n_head:
        return _grouped_rows_attention(
            q, k_new, v_new, kv, ts, n_head=n_head, n_kv_head=n_kv_head,
            scale=scale)
    S, T, Dkv = kv["k"].shape
    heads = (n_kv_head, Dkv // n_kv_head)
    rep = n_head // n_kv_head
    dt = jnp.float32 if "k_scale" in kv else kv["k"].dtype
    rows, live, pos = jnp.arange(S), ts >= 0, ts
    if q.ndim == 3:
        rows, live = rows[:, None], live[:, None]
        pos = ts[:, None] + jnp.arange(q.shape[1])[None, :]
    at = jnp.where(live, pos, T)            # idle -> out of range, dropped
    if window is not None:
        # where to write apart from how many rows are live: the mask
        # below (rows <= ts) is every row once ts has passed the ring
        at = jnp.where(live, pos % T, T)
    kv = {**_append(kv, "k", k_new, rows, at, heads),
          **_append(kv, "v", v_new, rows, at, heads)}
    pos_ok = (jnp.arange(T)[None, :] <= pos[..., None])[..., None, None, :]
    qg = (q * scale).astype(dt).reshape(
        q.shape[:-1] + (n_kv_head, rep, heads[1]))
    scores = jnp.einsum("s...grd,stgd->s...grt", qg, _read(kv, "k", heads),
                        preferred_element_type=jnp.float32)
    w = jax.nn.softmax(jnp.where(pos_ok, scores, -1e9), axis=-1)
    ctx = jnp.einsum("s...grt,stgd->s...grd", w.astype(dt),
                     _read(kv, "v", heads),
                     preferred_element_type=jnp.float32)
    return jnp.where(live[..., None], ctx.reshape(q.shape), 0.0), kv


def lane_masked_decode_attention(q, k_new, v_new, kv, ts, *, n_head: int,
                                 n_kv_head: int, scale: float):
    """The contract of :func:`grouped_masked_decode_attention` for one
    fresh row per slot over unquantized leaves, with the leaves read AS
    THEY LIE: no ``[S, T, n_kv_head, Dh]`` view of them is ever made.

    For a head narrower than a lane tile (``Dh`` 64) that view is not a
    bitcast on a TPU: the compiler re-tiles the leaf — a copy of the
    whole rung, K and V, every layer and step (seen in the compiled
    chunk of ``lfm2_24b_a2b``: four 537 MB copies a step, PR 40; since
    PR 57 that cell's even number of grouped 64-lane heads is the
    grouped kernel's, and this form keeps what :func:`step_read_sizes`
    refuses: see :func:`make_decode_attention`).  Here
    each query head is instead laid into its K/V head's ``Dh`` lanes of
    a row as wide as the leaf (zeros elsewhere), so the score product
    contracts the leaf's whole last axis and the context product yields
    whole-width rows of which the head keeps its own lanes: ``n_kv_head``
    times the arithmetic of a step that is bound by the leaves' bytes,
    and the same sums (the added terms are exact zeros)."""
    import jax
    import jax.numpy as jnp

    S, T, Dkv = kv["k"].shape
    heads = (n_kv_head, Dkv // n_kv_head)
    rep, D, dt = n_head // n_kv_head, heads[1], kv["k"].dtype
    rows, live = jnp.arange(S), ts >= 0
    at = jnp.where(live, ts, T)             # idle -> out of range, dropped
    kv = {**_append(kv, "k", k_new, rows, at, heads),
          **_append(kv, "v", v_new, rows, at, heads)}
    # own[h, c]: lane c of a leaf row belongs to query head h's K/V head
    own = (jnp.arange(n_head)[:, None] // rep
           == jnp.arange(Dkv)[None, :] // D)
    qh = (q * scale).reshape(S, n_head, D)
    qw = jnp.where(own[None], jnp.tile(qh, (1, 1, n_kv_head)), 0.0)
    scores = jnp.einsum("shc,stc->sht", qw.astype(dt), kv["k"],
                        preferred_element_type=jnp.float32)
    pos_ok = jnp.arange(T)[None, None, :] <= ts[:, None, None]
    w = jax.nn.softmax(jnp.where(pos_ok, scores, -1e9), axis=-1)
    wide = jnp.einsum("sht,stc->shc", w.astype(dt), kv["v"],
                      preferred_element_type=jnp.float32)
    ctx = jnp.sum(jnp.where(own[None], wide, 0.0).reshape(
        S, n_head, n_kv_head, D), axis=2)
    return jnp.where(live[:, None], ctx.reshape(S, n_head * D), 0.0), kv


def fresh_prompt_attention(q, k_new, v_new, like, *, n_head: int,
                           n_kv_head: int, scale: float):
    """The read half of the contract for ``C`` fresh rows a slot that
    START a sequence: row ``j`` of group row ``g`` is position ``j`` of
    its slot, so nothing any leaf holds is read — row ``j`` attends to
    the fresh rows ``<= j`` as the leaves will hold them (rounded to the
    storage dtype; int8: quantized per row and head as :func:`_append`
    quantizes them, dequantized at the read).  Returns ``(ctx`` shaped
    like ``q``, fp32, ``stored)``: the rows in storage form, a dict
    keyed like the layer's leaves (``k``, ``v`` ``[G, C, n_kv_head *
    Dh]``; int8 also ``k_scale``, ``v_scale`` ``[G, C, n_kv_head]``),
    for :func:`write_prompt_rows` — apart from the read, so that a
    forward can run its layers as ONE scanned body and write afterwards.

    ``q`` ``[G, C, n_head * Dh]``, ``k_new``, ``v_new`` ``[G, C,
    n_kv_head * Dh]`` fp32; ``like``: one layer's leaves, read for
    their dtype and for whether they carry scales, never for a value.
    Products in the storage dtype (int8: fp32), fp32 ones as fp32
    (``Precision.HIGHEST``: what the ragged kernel's step takes them
    in), fp32 accumulation and softmax."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.quant import dequantize_rows, quantize_rows

    G, C, _ = q.shape
    heads = (n_kv_head, like["k"].shape[-1] // n_kv_head)
    rep, quantized = n_head // n_kv_head, "k_scale" in like
    dt = jnp.float32 if quantized else like["k"].dtype
    stored = {}
    for name, new in (("k", k_new), ("v", v_new)):
        if quantized:
            codes, scales = quantize_rows(new.reshape((G, C) + heads))
            stored[name] = codes.reshape(new.shape)
            stored[name + "_scale"] = scales
        else:
            stored[name] = new.astype(like[name].dtype)

    def seen(name):
        x = stored[name].reshape((G, C) + heads)
        return (dequantize_rows(x, stored[name + "_scale"]) if quantized
                else x)

    exact = jax.lax.Precision.HIGHEST if dt == jnp.float32 else None
    qg = (q * scale).astype(dt).reshape((G, C, n_kv_head, rep, heads[1]))
    scores = jnp.einsum("gkhrd,gthd->gkhrt", qg, seen("k"), precision=exact,
                        preferred_element_type=jnp.float32)
    ok = jnp.arange(C)[None, :] <= jnp.arange(C)[:, None]       # [k, t]
    w = jax.nn.softmax(
        jnp.where(ok[None, :, None, None, :], scores, -1e9), axis=-1)
    ctx = jnp.einsum("gkhrt,gthd->gkhrd", w.astype(dt), seen("v"),
                     precision=exact, preferred_element_type=jnp.float32)
    return ctx.reshape(q.shape), stored


def write_prompt_rows(kv, stored, rows):
    """The append half: one layer's leaves with ``stored``
    (:func:`fresh_prompt_attention`'s rows in storage form, ``[G, C,
    ...]`` a leaf) written at positions ``0 .. C - 1`` of the slots
    ``rows`` ``[G]`` (each a slot; a caller with fewer seats than ``G``
    repeats one, which writes the same rows twice) — ONE
    ``dynamic_update_slice`` a leaf and group row, which touches ``C``
    rows of the rung and not the slot's whole row of it.  ``C`` may
    pass a prompt's end: what lands past it is
    rewritten by the step that reaches that position before anything
    reads it (the pool's write-before-read invariant).  Sequence leaves
    only (a ring leaf shorter than ``C`` would wrap)."""
    import jax

    C, T = stored["k"].shape[1], kv["k"].shape[KV_SEQ_AXIS]
    if C > T:
        raise ValueError("write_prompt_rows: %d fresh rows a slot over "
                         "leaves of %d positions" % (C, T))
    out = dict(kv)
    for name, fresh in stored.items():
        for g in range(fresh.shape[0]):
            out[name] = jax.lax.dynamic_update_slice(
                out[name], fresh[g][None], (rows[g], 0, 0))
    return out


def append_rows(kv, k_new, v_new, ts):
    """One layer's leaves with the fresh K/V rows appended in place —
    ``k_new``, ``v_new`` ``[S, n_kv_head * Dh]``: one a slot at ``ts``;
    ``[S, K, ...]``: row ``j`` at ``ts + j`` (idle slots, ``ts < 0``,
    are not written; a row at or past the rung's end is dropped): the
    append half of the contract alone, for a step that reads by a kernel
    that takes the leaves as they lie (:func:`grouped_decode_attention`,
    :func:`grouped_block_decode_attention`).  Unquantized leaves only."""
    import jax.numpy as jnp

    if "k_scale" in kv:
        raise ValueError("append_rows: int8 leaves are not supported")
    S, T, _ = kv["k"].shape
    rows, live, at = jnp.arange(S), ts >= 0, ts
    if k_new.ndim == 3:
        rows, live = rows[:, None], live[:, None]
        at = ts[:, None] + jnp.arange(k_new.shape[1])[None, :]
    at = jnp.where(live, at, T)             # out of range: dropped
    return {**_append(kv, "k", k_new, rows, at, None),
            **_append(kv, "v", v_new, rows, at, None)}


#: lanes of one tile of a leaf's minor axis in HBM
_LANE_TILE = 128


def _whole_tiles(lanes: int) -> int:
    return -(-int(lanes) // _LANE_TILE) * _LANE_TILE


def latent_leaves(n_rows: int, seq_len: int, d_latent: int, d_index,
                  dtype):
    """One latent-attention layer's zeroed leaves: ``latent`` ``[n_rows,
    seq_len, lanes]`` (the compressed row all heads share, its rotated
    lanes last) and — unless ``d_index`` is None: a layer that reads
    every live position keeps no scorer's key — ``index_k`` ``[n_rows,
    seq_len, lanes]`` (the scorer's key), in ``dtype``; every leaf's
    sequence axis is
    :data:`KV_SEQ_AXIS`.  ``lanes`` is the width rounded UP to whole
    128-lane tiles, the rest zeros (576 -> 640): the chip pads a row to
    whole tiles anyway, and a leaf DECLARED with a ragged last tile is
    re-laid sequence-minor inside the step's loop and copied whole there
    and back every dispatch (five 0.96 GB copies in the ``deepseek_v3_2``
    chunk, seen in its described-v5e compile, PR 54).  Two leaves and not
    one of both widths: the scorer reads every live ``index_k`` row and
    the attend only the selected ``latent`` rows, so neither read drags
    the other's lanes."""
    import jax.numpy as jnp

    out = {"latent": jnp.zeros((n_rows, seq_len, _whole_tiles(d_latent)),
                               dtype)}
    if d_index is not None:
        out["index_k"] = jnp.zeros((n_rows, seq_len, _whole_tiles(d_index)),
                                   dtype)
    return out


def pad_lanes(x, lanes: int):
    """``x`` with its last axis zero-padded to ``lanes`` (a latent leaf's
    row is whole 128-lane tiles: what meets it is padded to match)."""
    import jax.numpy as jnp

    pad = lanes - x.shape[-1]
    if not pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def append_latent_rows(kv, latent_new, index_new, ts):
    """A latent layer's leaves with the fresh rows appended in place:
    ``latent_new`` ``[S, d_latent]``, ``index_new`` ``[S, d_index]``
    (None where the layer keeps no ``index_k`` leaf), one a slot at
    ``ts``; or ``[S, K, ...]``: row ``j`` at ``ts + j`` (idle slots,
    ``ts < 0``, are not written; a row at or past the rung's end is
    dropped); each is zero-padded to its leaf's whole tiles."""
    import jax.numpy as jnp

    S, T, _ = kv["latent"].shape
    rows, live, at = jnp.arange(S), ts >= 0, ts
    if latent_new.ndim == 3:
        rows, live = rows[:, None], live[:, None]
        at = ts[:, None] + jnp.arange(latent_new.shape[1])[None, :]
    at = jnp.where(live, at, T)             # out of range: dropped
    return {name: _append(kv, name, pad_lanes(new, kv[name].shape[2]),
                          rows, at, None)[name]
            for name, new in (("latent", latent_new),
                              ("index_k", index_new)) if new is not None}


def _latent_softmax(s, ok, vals, d_value):
    """``softmax(s)`` over the allowed places times ``vals[..., :d_value]``:
    ``s`` ``[S, H, K]`` float32, ``ok`` ``[S, K]``, ``vals`` ``[S, K, D]``;
    a slot that may read nothing gets zeros."""
    import jax.numpy as jnp

    f32 = jnp.float32
    ok = ok[:, None, :]
    s = jnp.where(ok, s, _MASK)
    pr = jnp.exp(s - s.max(axis=-1, keepdims=True)) * ok
    pr = pr / jnp.maximum(pr.sum(axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("shk,skc->shc", pr.astype(vals.dtype),
                      vals[..., :d_value], preferred_element_type=f32)


def selected_latent_attention(q, kv, ts, sel, valid, *, d_value: int,
                              scale: float):
    """Read the positions named for each slot, ABSORBED: ``q`` ``[S, H,
    d_latent]`` float32 (each head's query already in the latent space,
    its rotated lanes last), ``kv`` a layer's latent leaves with this
    step's rows in (:func:`append_latent_rows`), ``sel`` ``[S, K]`` int32
    the positions slot ``s`` reads — the same for every head —, ``valid``
    ``[S, K]`` which of the list count (a named position past ``ts`` does
    not, whatever ``valid`` says).  Scores are ``scale * q . row`` over
    the whole row, the context is the weighed sum of the rows' first
    ``d_value`` lanes: ``[S, H, d_value]`` float32, zeros for a slot that
    reads nothing (idle: ``ts < 0``).  Products in the storage dtype,
    float32 accumulation and softmax; ``S * K`` rows leave HBM, not the
    rung.

    PRECONDITION: every slot's list is ASCENDING, UNIQUE and IN RANGE
    (``0 <= sel[s, j] < sel[s, j + 1] < T``), the places that are not
    ``valid`` included — what ``latent_sparse_lm.select_positions``
    returns.  The gather is told so (sorted, unique, in bounds); a list
    in any other order reads rows nobody named."""
    import jax.numpy as jnp

    LATENT_LOWERED.labels(path="xla").inc()
    leaf = kv["latent"]
    rows = leaf.at[jnp.arange(leaf.shape[0])[:, None], sel].get(
        indices_are_sorted=True, unique_indices=True,
        mode="promise_in_bounds")                               # [S, K, D]
    q = pad_lanes((q * scale).astype(leaf.dtype), leaf.shape[2])
    s = jnp.einsum("shd,skd->shk", q, rows,
                   preferred_element_type=jnp.float32)
    ok = valid & (sel <= ts[:, None])
    return _latent_softmax(s, ok, rows, d_value)


def masked_latent_attention(q, kv, allowed, *, d_value: int, scale: float):
    """A latent read's contract as a plain masked softmax over the WHOLE
    rung: slot ``s`` reads the positions ``allowed`` ``[S, T]`` marks.
    The parity reference of :func:`selected_latent_attention` and, a row
    at a time, of :func:`dense_latent_attention`
    (tests/test_latent_attention.py); no step takes it."""
    import jax.numpy as jnp

    leaf = kv["latent"]
    q = pad_lanes((q * scale).astype(leaf.dtype), leaf.shape[2])
    s = jnp.einsum("shd,std->sht", q, leaf,
                   preferred_element_type=jnp.float32)
    return _latent_softmax(s, allowed, leaf, d_value)


#: positions of the rung the dense latent read takes a turn of its walk
DENSE_LATENT_BLOCK = 512


def dense_latent_positions_touched(longest, rung: int,
                                   block: int = DENSE_LATENT_BLOCK):
    """Positions of a slot's rung the XLA form of
    :func:`dense_latent_attention` reads and multiplies when the pool's
    longest live context is ``longest`` (positions, the fresh rows among
    them): whole key blocks up to it, the same for EVERY slot — that
    form's host mirror, for a benchmark's share of what was touched that
    was live.  The kernel's path stops a slot at its own last fresh row:
    :func:`dense_latent_positions_read` answers for the lowering in
    force."""
    kb = divisor_block(int(rung), block)
    return min(-(-int(longest) // kb) * kb, int(rung))


def divisor_block(n: int, block: int) -> int:
    """The largest block of at most ``block`` positions that divides a
    rung of ``n`` (``block`` itself at every real size)."""
    kb = min(int(block), n)
    while n % kb:
        kb -= 1                        # tiny test rungs: a divisor
    return kb


def dense_latent_kernel_block(seq_len: int) -> int:
    """Positions of a key block of the dense latent kernel over a rung of
    ``seq_len``: :data:`_DENSE_LATENT_KERNEL_BLOCK`, a shorter rung
    itself."""
    return min(_DENSE_LATENT_KERNEL_BLOCK, int(seq_len))


def dense_latent_kernel_supported(seq_len: int, lanes: int, dtype, *,
                                  n_head: int, d_value: int,
                                  backend=None) -> bool:
    """Whether :func:`dense_latent_attention` lowers to its Pallas kernel
    over latent leaves ``[S, seq_len, lanes]`` of ``dtype`` under
    ``n_head`` heads: a TPU (``jax.default_backend()`` where ``backend``
    is unsaid), bf16 leaves, a row and the value part of it whole lane
    tiles, whole blocks (:func:`dense_latent_kernel_block`) of whole
    sublane tiles a rung, and the heads whole sublane tiles — so that ``K
    * n_head`` query rows are for every ``K``, and the step and the
    ``K``-row round of one builder take the same lowering."""
    import jax
    import jax.numpy as jnp

    block = dense_latent_kernel_block(seq_len)
    return ((backend or jax.default_backend()) == "tpu"
            and jnp.dtype(dtype) == jnp.bfloat16
            and lanes % _LANE_TILE == 0 and d_value % _LANE_TILE == 0
            and 0 < d_value <= lanes and block % 16 == 0
            and seq_len % block == 0 and n_head % 16 == 0)


def dense_latent_positions_read(ts, seq_len: int, *, lanes: int,
                                block: int = DENSE_LATENT_BLOCK, **leaves):
    """Positions :func:`dense_latent_attention` reads of a slot's leaf
    in a read whose (last) fresh row is at ``ts >= 0`` (a numpy integer
    array, the slots first; ``lanes``: a row's width, which
    :func:`latent_leaves` rounds up to whole tiles; ``leaves``: what else
    :func:`dense_latent_kernel_supported` takes after the rung), for the
    lowering in force: the kernel's whole key blocks
    (:func:`dense_latent_kernel_block`) up to the slot's OWN last fresh
    row, or the XLA form's whole blocks of ``block`` up to the LONGEST
    context of the slots read together, for every one of them
    (:func:`dense_latent_positions_touched`).  What the dense latent
    builder declares as its ``"kv"`` read (``decoding.PositionRead``)
    over bf16 leaves."""
    ts = np.asarray(ts)
    if dense_latent_kernel_supported(seq_len, _whole_tiles(lanes), **leaves):
        mine = dense_latent_kernel_block(seq_len)
        return kv_positions_read(ts, mine, mine)
    if not ts.size:
        return ts
    kb = divisor_block(int(seq_len), block)
    longest = ts.max(axis=0, keepdims=True) + 1
    return np.broadcast_to(np.minimum(-(-longest // kb) * kb, seq_len),
                           ts.shape)


def dense_latent_attention(q, kv, ts, *, d_value: int, scale: float,
                           key_block: int = DENSE_LATENT_BLOCK):
    """Read EVERY live position for ``K`` fresh rows a slot, ABSORBED:
    ``q`` ``[S, K, H, d_latent]`` float32 (each head's query already in
    the latent space, its rotated lanes last), ``kv`` a layer's latent
    leaves with this round's ``K`` rows in at ``ts .. ts + K - 1``
    (:func:`append_latent_rows`), ``ts`` ``[S]`` the first fresh row's
    position (``< 0``: an idle slot).  Row ``j`` reads the positions
    ``<= ts + j``; scores are ``scale * q . row`` over the whole row, the
    context the weighed sum of the rows' first ``d_value`` lanes: ``[S,
    K, H, d_value]`` float32, zeros for an idle slot.  Products in the
    storage dtype, float32 accumulation and softmax.

    The one shared row a position is read once for all ``K * H`` queries
    of its slot (a ``[K * H, lanes] x [lanes, block]`` product a slot:
    128 heads over 1,152 bytes, the one attention here whose least work
    is arithmetic and not bytes), a key block at a time under ONE running
    softmax, so no ``[S, K, H, T]`` score tensor is ever written
    (``key_block``: the XLA form's; the kernel's is its own,
    :func:`dense_latent_kernel_block`).  ONE algorithm, two lowerings
    chosen by what the code can observe
    (:func:`dense_latent_kernel_supported`; no knob):

    - the Pallas TPU kernel (:func:`dense_latent_kernel_attention`): each
      slot's OWN live key blocks from a work list, the leaf read as it
      lies, the running softmax in VMEM;
    - the XLA form (:func:`_dense_latent_xla`: the CPU, float32 leaves,
      rungs the block does not divide; the kernel's parity reference):
      batched over slots, so every slot walks the blocks of the pool's
      LONGEST live context and what lies past its own is multiplied and
      masked (:func:`dense_latent_positions_touched`).

    Both take the queries as ``[S, K * H, lanes]`` in the storage dtype
    and hand the context back ``[S, K * H, d_value]`` float32."""
    leaf = kv["latent"]
    S, T, lanes = leaf.shape
    K, H = q.shape[1], q.shape[2]
    if dense_latent_kernel_supported(T, lanes, leaf.dtype, n_head=H,
                                     d_value=d_value):
        LATENT_LOWERED.labels(path="dense_kernel").inc()
        return dense_latent_kernel_attention(
            q, kv, ts, d_value=d_value, scale=scale)
    LATENT_LOWERED.labels(path="dense_xla").inc()
    return _dense_latent_xla(
        _dense_latent_queries(q, leaf, scale), leaf, ts, heads=H,
        d_value=d_value, key_block=key_block).reshape(S, K, H, d_value)


def _dense_latent_queries(q, leaf, scale: float):
    """``q`` ``[S, K, H, d_latent]`` float32 as both lowerings of the
    dense read take it: scaled, in the leaf's dtype and lanes, the ``K``
    rows beside the heads — ``[S, K * H, lanes]``, one product a slot and
    block."""
    S, K, H, _ = q.shape
    lanes = leaf.shape[2]
    return pad_lanes((q * scale).astype(leaf.dtype), lanes).reshape(
        S, K * H, lanes)


def _dense_latent_xla(qs, leaf, ts, *, heads: int, d_value: int,
                      key_block: int):
    """The XLA form of :func:`dense_latent_attention`: ``qs`` ``[S, K *
    H, lanes]`` in the leaf's dtype, the context back ``[S, K * H,
    d_value]`` float32.  The rung is walked a block at a time for all
    slots together, up to the pool's longest live context and no
    further: a turn holds ``[S, K * H, key_block]`` scores."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    S, T, lanes = leaf.shape
    K, H = qs.shape[1] // heads, heads
    kb = divisor_block(T, key_block)
    # the last position each query may read; an idle slot's: none
    last = jnp.where(ts[:, None] >= 0,
                     ts[:, None] + jnp.arange(K)[None, :], -1)      # [S, K]
    last = jnp.repeat(last, H, axis=1)[:, :, None]          # [S, K * H, 1]

    def body(i, carry):
        m, l, acc = carry
        at = i * kb
        block = jax.lax.dynamic_slice(leaf, (0, at, 0), (S, kb, lanes))
        s = jnp.einsum("sqd,std->sqt", qs, block,
                       preferred_element_type=f32)
        ok = (at + jnp.arange(kb))[None, None, :] <= last
        s = jnp.where(ok, s, _MASK)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        pr = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
        acc = alpha[..., None] * acc + jnp.einsum(
            "sqt,stc->sqc", pr.astype(leaf.dtype), block[..., :d_value],
            preferred_element_type=f32)
        return m_new, alpha * l + pr.sum(axis=-1), acc

    n_blocks = (jnp.minimum(jnp.max(ts) + K, T) + kb - 1) // kb
    _, l, acc = jax.lax.fori_loop(
        0, n_blocks, body,
        (jnp.full((S, K * H), _MASK, f32), jnp.zeros((S, K * H), f32),
         jnp.zeros((S, K * H, d_value), f32)))
    return acc / jnp.maximum(l, 1e-30)[..., None]


#: positions of a (slot, key block) item of the dense latent kernel.  An
#: item's chain — score product, float32 softmax, value product, the
#: context's update — is serial and its fixed part (the queries re-packed
#: for the matrix unit, the ``[K * H, d_value]`` context read and written)
#: costs ~0.5 us whatever the block holds, so longer blocks pay it less
#: often and round a slot's context up further.  At ``[32,16384,640]``
#: bf16, K = 2, 128 heads, the cell's contexts (371,575 live positions in
#: 31 slots), ms a call and what is touched over what is live: 256 2.19 /
#: 1.012, 512 1.565 / 1.028, **1024 1.438 / 1.069 (77.4% of the bf16 peak
#: over what is live, 82.8% over what it touches)**, 2048 1.399 / 1.108;
#: the XLA form 2.425 in its blocks of 512 (62.7% over the 1.367 it
#: touches, 45.9% over what is live), 2.323 in 1024 — chip runs, PR 61,
#: tools/time_dense_latent.py.  1024 and not 2048: 2.7% for rounding
#: twice as coarse, which shorter contexts than the cell's would pay first
_DENSE_LATENT_KERNEL_BLOCK = 1024
#: key blocks whose reads are in flight ahead of the one the dense latent
#: kernel multiplies: a block's arithmetic outlasts its copy (blocks of
#: 1024: 1.420 ms a call with the copies cut out, 0.703 with the
#: arithmetic cut out — the copies alone run at 700 GB/s), so ONE read
#: ahead hides it: 1.438 with one, 1.446 with two (512: 1.565 / 1.578).
#: A form with an item's score product a turn ahead of its softmax in one
#: straight-line block (for the scheduler to overlap the matrix and the
#: vector unit) read 1.45 at 512 and 1.52 at 256 — better than the plain
#: walk at 256, no better at 512 — and was not kept (chip runs, PR 61)
_DENSE_LATENT_AHEAD = 1


def dense_latent_work_items(ts, fresh: int, seq_len: int, block: int):
    """The dense latent kernel's work list: the live ``(slot, key
    block)`` pairs of a read of ``fresh`` rows a slot at ``ts .. ts +
    fresh - 1``, slot-major — whole blocks up to the one that holds the
    slot's OWN last fresh row (:func:`last_fresh_row`), none for an idle
    slot.  ``(n_items [1], slot, blk)`` as :func:`decode_work_items`
    gives them (entries past ``n_items`` are padding)."""
    n_items, slot, blk, _ = decode_work_items(
        last_fresh_row(ts, fresh, seq_len), seq_len, block, block)
    return n_items, slot, blk


def _dense_latent_kernel(n_items_ref, item_slot_ref, item_blk_ref, ts_ref,
                         nth_ref,                               # SMEM
                         q_hbm, leaf_hbm,                       # HBM (ANY)
                         o_hbm,                                 # output
                         qbuf, kbuf, m_ref, l_ref, acc_ref, obuf, sem, *,
                         block, heads, d_value):
    """The work list's items one after another, the reads of the next
    ``ahead`` in flight: an item is ONE ``[block, lanes]`` slab of the
    leaf as it lies, scored against the slot's ``[K * H, lanes]`` queries
    in one product contracted over the lanes of both (:func:`_block_part`:
    the slab is never transposed), its first ``d_value`` lanes weighed in
    one more.  A slot's queries come in by a copy of their own, started
    with its block 0's (``nth_ref``: the slot's number among the live
    ones, which of the query buffers it takes); its max, sum and context
    stay in VMEM from its block 0 to the block of its last fresh row,
    where the context is divided and leaves by a copy that the next
    slot's products hide.  Query row ``r`` is fresh row ``r // heads`` and
    reads the positions ``<= ts + r // heads``: only the blocks from the
    one that holds ``ts`` on are masked, the others traced without a
    select.  A block an earlier row cannot see at all leaves its sums as
    they were (:func:`_grouped_kernel` says why).  An idle slot is
    written zeros."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = qbuf.shape[1]
    S, T, _ = leaf_hbm.shape
    nbuf = kbuf.shape[0]
    ahead, fresh = nbuf - 1, rows // heads
    n_items = n_items_ref[0]

    def context_out(n):
        return pltpu.make_async_copy(obuf, o_hbm.at[n], sem.at[2, 0])

    obuf[...] = jnp.zeros_like(obuf)

    def idle(n, carry):
        @pl.when(ts_ref[n] < 0)
        def _():
            context_out(n).start()
            context_out(n).wait()

        return carry

    jax.lax.fori_loop(0, S, idle, 0)

    def slab_read(i):
        n, buf = item_slot_ref[i], jax.lax.rem(i, nbuf)
        src = pl.ds(pl.multiple_of(item_blk_ref[i] * block, block), block)
        return pltpu.make_async_copy(leaf_hbm.at[n, src], kbuf.at[buf],
                                     sem.at[0, buf])

    def query_read(n):
        buf = jax.lax.rem(nth_ref[n], nbuf)
        return pltpu.make_async_copy(q_hbm.at[n], qbuf.at[buf],
                                     sem.at[1, buf])

    def score(i):
        n, b, buf = item_slot_ref[i], item_blk_ref[i], jax.lax.rem(i, nbuf)
        t, qb = ts_ref[n], jax.lax.rem(nth_ref[n], nbuf)

        @pl.when(b == 0)
        def _():
            query_read(n).wait()
            m_ref[...] = jnp.full_like(m_ref, _MASK)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def take_in(masked):
            def body():
                ok = None
                if masked:
                    row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
                    at = t + sum((row >= j * heads).astype(jnp.int32)
                                 for j in range(1, fresh))
                    ok = b * block + jax.lax.broadcasted_iota(
                        jnp.int32, (rows, block), 1) <= at
                # ONE load of the slab: a bf16 operand is re-packed from
                # the leaf's HBM tiling to the matrix unit's as it is
                # loaded, and the value lanes are the slab's own
                slab = kbuf[buf]
                m, l, acc = _block_part(
                    qbuf[qb], slab, slab[:, :d_value], ok,
                    m_ref[:, :1], l_ref[:, :1], acc_ref[...])
                m_ref[...] = jnp.broadcast_to(m, m_ref.shape)
                l_ref[...] = jnp.broadcast_to(l, l_ref.shape)
                acc_ref[...] = acc

            return body

        first_masked = t // block   # the first block a row sees part of
        pl.when(b >= first_masked)(take_in(True))
        pl.when(b < first_masked)(take_in(False))

        @pl.when(b == jnp.minimum(t + fresh - 1, T - 1) // block)
        def _():
            @pl.when(nth_ref[n] > 0)
            def _():
                context_out(n).wait()   # the slot before's: long landed

            obuf[...] = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
            context_out(n).start()

    def turn(j, carry):
        @pl.when(j < n_items)
        def _():
            slab_read(j).start()

            @pl.when(item_blk_ref[j] == 0)
            def _():
                query_read(item_slot_ref[j]).start()

        i = j - ahead

        @pl.when(i >= 0)
        def _():
            slab_read(i).wait()
            score(i)

        return carry

    jax.lax.fori_loop(0, n_items + ahead, turn, 0)

    @pl.when(n_items > 0)
    def _():
        context_out(0).wait()


def dense_latent_kernel_attention(q, kv, ts, *, d_value: int, scale: float,
                                  key_block=None, interpret=False):
    """:func:`dense_latent_attention`'s contract through the Pallas TPU
    kernel whatever the backend (``interpret``: the CPU's tests; the
    tools): what the chooser there takes for shapes
    :func:`dense_latent_kernel_supported` accepts.  ``key_block`` unsaid
    is :func:`dense_latent_kernel_block` of the rung; said (the tests'
    small rungs, the tool's sweep), it divides the rung."""
    leaf = kv["latent"]
    S, K, H, _ = q.shape
    return _dense_latent_call()(
        ts, _dense_latent_queries(q, leaf, scale), leaf, heads=H,
        d_value=d_value,
        block=key_block or dense_latent_kernel_block(leaf.shape[1]),
        ahead=_DENSE_LATENT_AHEAD, interpret=interpret).reshape(
            S, K, H, d_value)


@functools.lru_cache(maxsize=None)
def _dense_latent_call():
    """One jitted entry point for every call site (:func:`_kernel_call`
    says why): a round's layers and its module share one trace."""
    import jax

    return jax.jit(_dense_latent, static_argnames=(
        "heads", "d_value", "block", "ahead", "interpret"))


def _dense_latent(ts, qs, leaf, *, heads, d_value, block, ahead, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, T, lanes = leaf.shape
    rows = qs.shape[1]
    dt, f32, i32 = leaf.dtype, jnp.float32, jnp.int32
    size = jnp.dtype(dt).itemsize
    ts = ts.astype(i32)
    work = dense_latent_work_items(ts, rows // heads, T, block)
    nth = jnp.cumsum((ts >= 0).astype(i32)) - 1
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    nbuf = ahead + 1
    # bytes the kernel keeps in VMEM: the slabs and queries being read
    # and scored, a slot's sums and context twice, a block's scores a few
    # times over
    resident = (nbuf * (rows + block) * lanes * size
                + 2 * 4 * rows * (_HEAD_LANES + d_value)
                + 4 * 4 * rows * block)
    return pl.pallas_call(
        functools.partial(_dense_latent_kernel, block=block, heads=heads,
                          d_value=d_value),
        out_shape=jax.ShapeDtypeStruct((S, rows, d_value), f32),
        in_specs=[smem] * 5 + [hbm] * 2,
        out_specs=hbm,
        scratch_shapes=[
            pltpu.VMEM((nbuf, rows, lanes), dt),
            pltpu.VMEM((nbuf, block, lanes), dt),
            pltpu.VMEM((rows, _HEAD_LANES), f32),       # a slot's max,
            pltpu.VMEM((rows, _HEAD_LANES), f32),       # sum
            pltpu.VMEM((rows, d_value), f32),           # and weighted rows
            pltpu.VMEM((rows, d_value), f32),           # a context going out
            pltpu.SemaphoreType.DMA((3, nbuf)),
        ],
        compiler_params=(pltpu.CompilerParams(
            vmem_limit_bytes=min(100 << 20, 2 * resident))
            if resident > _VMEM_DEFAULT * 3 // 4 else None),
        name="dense_latent_attention",
        interpret=interpret,
    )(*work, ts, nth, qs, leaf)


def block_kernel_supported(kv, n_head: int, n_kv_head: int,
                           block: int) -> bool:
    """Shapes and dtypes :func:`block_sparse_decode_attention` lowers
    for: unquantized leaves of whole blocks, a head's lanes a whole
    number of lane tiles, blocks and the query heads of a group whole
    sublane tiles."""
    import jax.numpy as jnp

    width = kv["k"].shape[-1]
    rows = 16 if kv["k"].dtype == jnp.bfloat16 else 8
    return ("k_scale" not in kv and width % n_kv_head == 0
            and (width // n_kv_head) % 128 == 0 and block % rows == 0
            and kv["k"].shape[KV_SEQ_AXIS] % block == 0
            and (n_head // n_kv_head) % rows == 0
            and kv["k"].dtype in (jnp.bfloat16, jnp.float32))


def _score_chunks(rows: int, block: int, score_rows: int):
    """``(chunk, chunks)``: a unit of ``rows`` keys is scored ``chunk``
    at a time (whole blocks, ``score_rows`` at most but a block at
    least), chunk ``c`` from row ``min(c * chunk, rows - chunk)``: the
    last one is drawn back inside the unit, and the rows it shares with
    the one before count once."""
    chunk = min(rows, max(score_rows // block, 1) * block)
    return chunk, -(-rows // chunk)


def _block_part(q, k, v, ok, m, l, acc):
    """One chunk of keys into a (slot, K/V head)'s online softmax: ``q``
    ``[rep, Dh]`` against ``k``, ``v`` ``[N, Dh]`` in the storage dtype,
    ``ok`` ``[rep, N]`` the positions that may be read (None: all of
    them, and no select is traced); ``m``, ``l``
    ``[rep, 1]`` and ``acc`` ``[rep, Dh]`` fp32 the max, the sum and the
    weighted rows so far (``(_MASK, 0, 0)``: nothing yet).  Returns the
    three with the chunk taken in."""
    import jax
    import jax.numpy as jnp

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if ok is not None:
        s = jnp.where(ok, s, _MASK)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    if ok is not None:
        p = jnp.where(ok, p, 0.0)
    return (m_new, alpha * l + jnp.sum(p, axis=1, keepdims=True),
            alpha * acc + jnp.dot(p.astype(v.dtype), v,
                                  preferred_element_type=jnp.float32))


def _block_kernel(ts_ref, plan_ref,                             # SMEM
                  q_ref, pos_ref,                               # VMEM
                  k_hbm, v_hbm,                                 # HBM (ANY)
                  o_ref,                                        # output
                  fk, fv, tk, tv, m_ref, l_ref, acc_ref, sem,
                  *, runs, n_tiles, block, score_rows, tile_unroll):
    """One slot after another, a slot in ``1 + G`` units: the declared
    runs (both leaves' whole-width slabs, every head scored from them),
    then each K/V head's own tiles.  The reads of the next
    ``min(2, units - 1)`` units are in flight while one is scored.

    The body is written ONCE a unit kind — one routine that starts a
    kind's reads, one that scores it — and reached through ONE loop over
    (slot, unit) with the unit a loop variable (a head is a leading
    index of the tile buffers, a lane offset into the slabs; the turns
    before slot 0 only start reads, so there is no prologue); a unit is
    scored ``score_rows`` keys at a time in a loop, the online softmax's
    state in VMEM; the positions of a head's tiles come in from the
    wrapper (``pos_ref``).  What a process pays to TRACE a kernel grows
    with the equations of its body, compile cache hit or not: the same
    read with the units, heads and prologue written out and the tiles'
    positions put together from 64 scalars a head in the body was 2,317
    equations for these 277 and cost every process of the cell 8-9 s
    (PR 41; ``tests/test_decode_attention.py`` holds the count)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, G, rep, D = q_ref.shape
    T = k_hbm.shape[1]
    dt, f32, i32 = fk.dtype, jnp.float32, jnp.int32
    # a slot's line of the plan: the runs' first rows, the first and the
    # last positions that count in them, then every head's tiles
    width = 3 * len(runs) + G * n_tiles

    def run_at(n, i, what):
        return plan_ref[n * width + what * len(runs) + i]

    def tile_at(n, g, j):
        return plan_ref[n * width + 3 * len(runs) + g * n_tiles + j]

    # a slot's units: the runs' slabs (if any were declared: unit 0),
    # then a head's tiles (if any entry lies outside the runs)
    slabs = int(bool(runs))
    units = slabs + (G if n_tiles else 0)
    ahead = min(2, units - 1)
    spans = [sum(runs[:i]) for i in range(len(runs) + 1)]   # rows in fk, fv

    def start_runs(n):
        for i in range(len(runs)):
            src = pl.ds(pl.multiple_of(run_at(n, i, 0), block), runs[i])
            dst = pl.ds(spans[i], runs[i])
            pltpu.make_async_copy(k_hbm.at[n, src], fk.at[dst],
                                  sem.at[0, 0]).start()
            pltpu.make_async_copy(v_hbm.at[n, src], fv.at[dst],
                                  sem.at[1, 0]).start()

    def start_tiles(n, g):
        lanes = pl.ds(pl.multiple_of(g * D, D), D)  # a head's lane tiles

        def some(i, carry):
            for j in (i * turn + u for u in range(turn)):
                b = jnp.maximum(tile_at(n, g, j), 0)
                src = pl.ds(pl.multiple_of(b * block, block), block)
                dst = pl.ds(pl.multiple_of(j * block, block), block)
                pltpu.make_async_copy(k_hbm.at[n, src, lanes], tk.at[g, dst],
                                      sem.at[0, 1 + g]).start()
                pltpu.make_async_copy(v_hbm.at[n, src, lanes], tv.at[g, dst],
                                      sem.at[1, 1 + g]).start()
            return carry

        # Mosaic unrolls a loop wholly or not at all: a turn's reads are
        # written out
        turn = max(u for u in range(1, tile_unroll + 1) if n_tiles % u == 0)
        jax.lax.fori_loop(0, n_tiles // turn, some, 0)

    def of_kind(u, do_runs, do_tiles):
        """Unit ``u`` (traced) of a slot handed to its kind's routine."""
        if runs:
            pl.when(u == 0)(do_runs)
        if n_tiles:
            pl.when(u >= slabs)(lambda: do_tiles(u - slabs))

    def wait(buf, leaf, at):
        """ONE wait a leaf, sized as the unit's whole buffer, takes in
        all of its reads (they share the semaphore)."""
        pltpu.make_async_copy(buf, buf, sem.at[leaf, at]).wait()

    def score(n, g, kbuf, vbuf, rows, pos_of, first):
        """Head ``g``'s queries of slot ``n`` against the ``rows`` keys
        of a unit (``kbuf``, ``vbuf`` ``[rows, D]``), a chunk at a time
        (:func:`_score_chunks`), into the head's online softmax (begun
        here if ``first``).  ``pos_of(c, r0, chunk)``: the positions
        ``[1, chunk]`` of chunk ``c``'s rows, which start at ``r0``; ``T``
        for a row that may not be read or that an earlier chunk counted."""
        chunk, n_chunks = _score_chunks(rows, block, score_rows)
        t, q = ts_ref[n], q_ref[n, g]
        if first:
            m_ref[g] = jnp.full((rep, _HEAD_LANES), _MASK, f32)
            l_ref[g] = jnp.zeros((rep, _HEAD_LANES), f32)
            acc_ref[g] = jnp.zeros((rep, D), f32)

        def some(c, carry):
            # the last chunk is drawn back inside the unit
            r0 = 0 if n_chunks == 1 else pl.multiple_of(
                jnp.minimum(c * chunk, rows - chunk), block)
            ok = jnp.broadcast_to(pos_of(c, r0, chunk), (rep, chunk)) <= t
            m, l, acc = _block_part(
                q, kbuf[pl.ds(r0, chunk), :], vbuf[pl.ds(r0, chunk), :], ok,
                m_ref[g, :, :1], l_ref[g, :, :1], acc_ref[g])
            m_ref[g] = jnp.broadcast_to(m, (rep, _HEAD_LANES))
            l_ref[g] = jnp.broadcast_to(l, (rep, _HEAD_LANES))
            acc_ref[g] = acc
            return carry

        if n_chunks == 1:
            some(0, 0)
        else:
            jax.lax.fori_loop(0, n_chunks, some, 0)

    def finish(n, g):
        o_ref[n, g] = acc_ref[g] / jnp.maximum(l_ref[g, :, :1], 1e-30)

    def score_runs(n):
        wait(fk, 0, 0)
        wait(fv, 1, 0)

        def pos_of(c, r0, chunk):
            """Row ``r`` of run ``i`` is position ``row_i + r``: the
            run's blocks lie one after another."""
            row = r0 + jax.lax.broadcasted_iota(i32, (1, chunk), 1)
            pos = jnp.full(row.shape, T, i32)
            for i in range(len(runs)):
                at = run_at(n, i, 0) + row - spans[i]
                mine = ((row >= spans[i]) & (row < spans[i + 1])
                        & (at >= run_at(n, i, 1)) & (at <= run_at(n, i, 2)))
                pos = jnp.where(mine, at, pos)
            if spans[-1] % chunk:   # rows the chunk before has counted
                pos = jnp.where(row >= c * chunk, pos, T)
            return pos

        def head(g, carry):
            lanes = pl.ds(pl.multiple_of(g * D, D), D)
            score(n, g, fk.at[:, lanes], fv.at[:, lanes], spans[-1], pos_of,
                  first=True)
            if not n_tiles:
                finish(n, g)
            return carry

        jax.lax.fori_loop(0, G, head, 0)

    def score_tiles(n, g):
        wait(tk.at[g], 0, 1 + g)
        wait(tv.at[g], 1, 1 + g)

        _, n_chunks = _score_chunks(n_tiles * block, block, score_rows)

        def pos_of(c, r0, chunk):
            # the wrapper laid them out a line a (slot, head, chunk)
            return pos_ref[pl.ds((n * G + g) * n_chunks + c, 1), :]

        score(n, g, tk.at[g], tv.at[g], n_tiles * block, pos_of,
              first=not runs)
        finish(n, g)

    def step(_, at):
        # ``at``: the (slot, unit) scored this turn; the turns before
        # slot 0 only start reads
        n, u = at
        over = (u + ahead >= units).astype(i32)
        n2, u2 = n + over, u + ahead - units * over

        @pl.when(n2 < S)
        def _():
            of_kind(u2, lambda: start_runs(n2), lambda g: start_tiles(n2, g))

        @pl.when(n >= 0)
        def _():
            of_kind(u, lambda: score_runs(n), lambda g: score_tiles(n, g))

        last = (u + 1 == units).astype(i32)
        return n + last, (u + 1) * (1 - last)

    first = (jnp.int32(-1), jnp.int32(units - ahead)) if ahead else (
        jnp.int32(0), jnp.int32(0))
    jax.lax.fori_loop(0, S * units + ahead, step, first)


def block_sparse_decode_attention(q, k_cache, v_cache, ts, blocks, valid, *,
                                  n_head: int, n_kv_head: int, scale: float,
                                  block: int, shared_runs=(),
                                  interpret=False):
    """The Pallas TPU kernel of "read the blocks named for this row".

    ``q`` ``[S, n_head * Dh]`` fp32; ``k_cache``, ``v_cache`` ``[S, T,
    n_kv_head * Dh]`` (read only: :func:`append_rows` has written the
    step's rows); ``ts`` ``[S]``; ``blocks``, ``valid`` ``[S, n_kv_head,
    B]``.  Returns ctx ``[S, n_kv_head, rep, Dh]`` fp32: zeros where
    nothing may be read.

    ``shared_runs``: ``((first entry, entries), ...)`` — what the CALLER
    declares of its lists (static: a DMA's length is): those entries are
    the same in every head's list and, where valid, name consecutive
    blocks in rising order, the valid ones next to each other.  Such a
    run lies in a leaf as ONE stretch of rows, so it is read as it lies:
    whole-width ``[entries * block, n_kv_head * Dh]`` slabs, K and V one
    copy each a slot, from which every head's lanes are cut in VMEM
    (a 128-lane slice is a tile).  The stretch starts at the first valid
    block's first row, or as far before it as keeps it inside the leaf;
    its rows count by position (no earlier than that block, no later
    than the last valid one's end, ``<= ts``), so ids clamped to the
    rung's end and entries masked off (a window that meets the first
    block) are not read twice.  Every other entry is a head's own
    ``[block, Dh]`` tile, fetched by its own DMA into the head's buffer.

    The kernel walks the slots in ``1 + n_kv_head`` units each — the
    runs' slabs, then each head's tiles — with the next two units' reads
    in flight while one is scored (the ragged kernel's ``_READS_AHEAD``,
    by hand here too).  A unit is scored :data:`_SCORE_ROWS` keys at a
    time: a chunk's keys against the group's ``[rep, Dh]`` query heads
    on the MXU, its softmax, one more product for the weighted rows, all
    taken into the head's online softmax, which a head's units share.
    One jitted entry point for every call site (:func:`_kernel_call`
    says why), and a body that a process traces and lowers in about the
    time of the BlockSpec kernel it replaced (:func:`_block_kernel`)."""
    return _block_call()(
        q, k_cache, v_cache, ts, blocks, valid, n_head=n_head,
        n_kv_head=n_kv_head, scale=float(scale), block=block,
        shared_runs=tuple((int(a), int(b)) for a, b in shared_runs),
        score_rows=_SCORE_ROWS, tile_unroll=_TILE_UNROLL, interpret=interpret)


@functools.lru_cache(maxsize=None)
def _block_call():
    import jax

    return jax.jit(_block_sparse, static_argnames=(
        "n_head", "n_kv_head", "scale", "block", "shared_runs", "score_rows",
        "tile_unroll", "interpret"))


def _block_sparse(q, k_cache, v_cache, ts, blocks, valid, *, n_head,
                  n_kv_head, scale, block, shared_runs, score_rows,
                  tile_unroll, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, T, Dkv = k_cache.shape
    G, D, rep = n_kv_head, Dkv // n_kv_head, n_head // n_kv_head
    B = blocks.shape[-1]
    dt, f32, i32 = k_cache.dtype, jnp.float32, jnp.int32
    # a run longer than the leaf is no stretch of it: its entries are tiles
    shared_runs = tuple((a, n) for a, n in shared_runs
                        if 0 < n * block <= T)
    named = jnp.clip(jnp.where(valid, blocks, -1).astype(i32), -1,
                     T // block - 1)
    # of[i, e]: entry e of a list belongs to run i; lone[e]: to none (a
    # head's own tile)
    of = np.zeros((len(shared_runs), B), bool)
    for i, (a, n) in enumerate(shared_runs):
        of[i, a:a + n] = True
    lone = ~of.any(axis=0)
    plan = []       # a slot's line: _block_kernel reads it by run_at, tile_at
    if shared_runs:
        ids = jnp.where(of[None], named[:, :1], -1)         # [S, runs, B]
        first = jnp.min(jnp.where(ids >= 0, ids, T), axis=2) * block
        room = T - block * np.asarray([n for _, n in shared_runs])
        plan = [jnp.clip(first, 0, room[None]), first,
                jnp.max(ids, axis=2) * block + block - 1]
    # the entries outside the runs, in the list's order: a head's own tiles
    n_tiles = int(lone.sum())
    tiles = named[:, :, np.flatnonzero(lone)]                # [S, G, tiles]
    plan = jnp.concatenate(plan + [tiles.reshape(S, -1)], axis=1)
    # the position of every row of a head's tiles as they will lie in its
    # buffer (T, which no slot reaches, under an entry that names
    # nothing), a line a chunk the kernel scores (_score_chunks: a row the
    # chunk before has counted reads T in the last, drawn-back one)
    tile_pos = jnp.zeros((8, _HEAD_LANES), i32)     # no tiles: not read
    if n_tiles:
        flat = (jnp.where(tiles >= 0, tiles * block, T)[..., None]
                + jnp.arange(block, dtype=i32)).reshape(S * G, -1)
        chunk, n_chunks = _score_chunks(n_tiles * block, block, score_rows)
        tile_pos = jnp.stack([
            jnp.where(np.arange(a, a + chunk) >= c * chunk,
                      flat[:, a:a + chunk], T)
            for c, a in enumerate(min(c * chunk, n_tiles * block - chunk)
                                  for c in range(n_chunks))],
            axis=1).reshape(-1, chunk)
    runs = tuple(n * block for _, n in shared_runs)     # rows of a slab
    run_rows = sum(runs)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    size = jnp.dtype(dt).itemsize
    # bytes the kernel keeps in VMEM: q, the tiles' positions and the
    # context whole, every unit's buffers, a chunk's scores a few times
    resident = (S * G * rep * D * (size + 4) + 4 * tile_pos.size
                + 2 * size * (run_rows * Dkv + G * n_tiles * block * D)
                + 4 * 4 * rep * min(score_rows, max(run_rows,
                                                    n_tiles * block)))
    return pl.pallas_call(
        functools.partial(_block_kernel, runs=runs, n_tiles=n_tiles,
                          block=block, score_rows=score_rows,
                          tile_unroll=tile_unroll),
        out_shape=jax.ShapeDtypeStruct((S, G, rep, D), f32),
        in_specs=[smem] * 2 + [vmem] * 2 + [hbm] * 2,
        out_specs=vmem,
        scratch_shapes=[
            pltpu.VMEM((max(run_rows, 8), Dkv), dt),
            pltpu.VMEM((max(run_rows, 8), Dkv), dt),
            pltpu.VMEM((G, max(n_tiles * block, 8), D), dt),
            pltpu.VMEM((G, max(n_tiles * block, 8), D), dt),
            pltpu.VMEM((G, rep, _HEAD_LANES), f32),     # a head's max,
            pltpu.VMEM((G, rep, _HEAD_LANES), f32),     # sum
            pltpu.VMEM((G, rep, D), f32),               # and weighted rows
            pltpu.SemaphoreType.DMA((2, 1 + G)),
        ],
        # asked for only by lists longer than the compiler's default holds
        compiler_params=(pltpu.CompilerParams(
            vmem_limit_bytes=min(100 << 20, 2 * resident))
            if resident > _VMEM_DEFAULT * 3 // 4 else None),
        name="block_sparse_decode_attention",
        interpret=interpret,
    )(ts.astype(i32), plan.reshape(-1),
      (q * scale).astype(dt).reshape(S, G, rep, D),
      tile_pos, k_cache, v_cache)


def _grouped_kernel(n_items_ref, item_slot_ref, item_blk_ref, item_rows_ref,
                    ts_ref,                                     # SMEM
                    q_ref,                                      # VMEM
                    *refs, block, tail, heads, head_rows, fresh, pair):
    """The work list's items one after another, the reads of the next
    ``ahead`` in flight: an item is BOTH leaves' whole-width ``[rows,
    n_kv_head * Dh]`` slabs, one copy each, scored a UNIT of ``heads``
    K/V heads at a time from the unit's lanes of the slabs
    (:func:`_block_part`; the online softmax's state in VMEM, begun at a
    slot's block 0 and written out at its last).

    ``q_ref`` ``[S, units, R, L]``: a unit's query rows against its ``L =
    heads * Dh`` lanes — head ``h`` of the unit in rows ``h * rep_p ..``,
    its own ``Dh`` lanes filled and the unit's other lanes zero, so one
    product scores the unit's heads and one more weighs their rows (the
    zeros add exact zeros; a head keeps its own lanes of the result).
    ``head_rows`` (static, :func:`_head_rows`) is ``rep_p``; ``pair``
    (static, :func:`_heads_a_tile`) the heads whose contexts go out as
    one tile ``[rep_p, pair * Dh]``, each its own lanes of it (64-lane
    heads: two a lane tile; else one).  At ONE row
    a head the unit's heads are consecutive rows and ``q_ref`` is ``[S,
    units, R, Dh]``: the rows are laid into their lanes here (a select
    over ``heads`` copies side by side: 60 vector selects an item at 30
    heads, where the wide rows as an operand were 20 MB of VMEM and 4-6%
    of a call), and the context goes out ``[S, units, R, Dh]``, row ``h``
    its head's own lanes of the weighted rows.

    ``fresh`` (static) is ``K``, the fresh rows a slot: row ``r`` of a
    head is the slot's fresh row ``r // rep`` and reads the positions
    ``<= ts + r // rep``.  Which fresh row a query row is comes in as one
    more VMEM operand after ``q_ref`` (``[R, 128]`` int32, a row's index
    across its lanes) where ``K > 1``; at ``K = 1`` there is none and
    the program is the one-row kernel's.  The work list is the LAST
    row's, so a slot's state is written out at the block that holds
    ``ts + K - 1`` (the rung's last where that passes its end).  A block
    an earlier row cannot see at all is a fully masked block for that
    row: every score is the finite ``_MASK``, the running maximum —
    begun at block 0, where every live row sees position 0 — stays what
    it was, ``alpha`` is 1 and every weight an exact 0 (``_block_part``
    masks ``p`` too), so the row's sums are unchanged, not NaN.

    Written once: ONE loop over the turns (turn ``j`` starts item
    ``j``'s reads and scores item ``j - ahead``, so the turns before
    item 0 are the prologue), one class switch for the starts and one
    for the waits (a DMA's length is static), ONE scoring routine over
    the whole block whatever the item's rows (rows past them are masked
    by position; the V buffers are zeroed once so that no stale row
    weighs in as ``0 * nan``)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    row_ref = refs[0] if fresh > 1 else None
    (k_hbm, v_hbm,                                              # HBM (ANY)
     o_ref,                                                     # output
     kbuf, vbuf, m_ref, l_ref, acc_ref, sem) = refs[fresh > 1:]
    rep_p, D = head_rows, o_ref.shape[-1] // pair
    _, units, R, _ = q_ref.shape
    L = heads * D
    T = k_hbm.shape[1]
    nbuf = kbuf.shape[0]
    ahead = nbuf - 1
    n_items = n_items_ref[0]
    classes = list(range(tail, block + 1, tail))
    o_ref[...] = jnp.zeros_like(o_ref)      # idle slots: zero context
    vbuf[...] = jnp.zeros_like(vbuf)

    def reads(i, rows):
        n, b, buf = item_slot_ref[i], item_blk_ref[i], jax.lax.rem(i, nbuf)
        src = pl.ds(pl.multiple_of(b * block, block), rows)
        dst = pl.ds(0, rows)
        return (pltpu.make_async_copy(k_hbm.at[n, src], kbuf.at[buf, dst],
                                      sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[n, src], vbuf.at[buf, dst],
                                      sem.at[1, buf]))

    def start(i, rows):
        for c in reads(i, rows):
            c.start()

    def wait(i, rows):  # takes the descriptor the read was started with
        for c in reads(i, rows):
            c.wait()

    def score(i):
        n, b, buf = item_slot_ref[i], item_blk_ref[i], jax.lax.rem(i, nbuf)
        t = ts_ref[n]

        @pl.when(b == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, _MASK)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # a query row reads up to its own position; the slot is done at
        # the block of its last row's
        at = t if row_ref is None else t + row_ref[:, :1]
        last = t if fresh == 1 else jnp.minimum(t + fresh - 1, T - 1)
        ok = b * block + jax.lax.broadcasted_iota(
            jnp.int32, (R, block), 1) <= at

        def unit(u, carry):
            lanes = slice(None) if units == 1 else pl.ds(
                pl.multiple_of(u * L, L), L)
            q = q_ref[n, u]
            if q.shape[1] < L:      # [R, D]: head h's row, its lanes alone
                lane = jax.lax.broadcasted_iota(jnp.int32, (R, L), 1)
                row = jax.lax.broadcasted_iota(jnp.int32, (R, L), 0) * D
                q = jnp.where((lane >= row) & (lane < row + D),
                              jnp.concatenate([q] * heads, axis=1),
                              jnp.zeros((), q.dtype))
            m, l, acc = _block_part(
                q, kbuf[buf, :, lanes], vbuf[buf, :, lanes], ok,
                m_ref[u, :, :1], l_ref[u, :, :1], acc_ref[u])
            m_ref[u] = jnp.broadcast_to(m, (R, _HEAD_LANES))
            l_ref[u] = jnp.broadcast_to(l, (R, _HEAD_LANES))
            acc_ref[u] = acc

            def head_tiles():
                for h in range(0, heads, pair):
                    # a head's own rows over its tile's lanes; of two
                    # 64-lane heads a tile each keeps its own half
                    own = [acc[rows, h * D:(h + pair) * D] / l[rows]
                           for rows in (slice(j * rep_p, (j + 1) * rep_p)
                                        for j in range(h, h + pair))]
                    o_ref[n, u * (heads // pair) + h // pair] = (
                        own[0] if pair == 1 else jnp.where(
                            jax.lax.broadcasted_iota(
                                jnp.int32, own[0].shape, 1) < D, *own))

            def unit_rows():        # one row a head: row h, its own lanes
                row = jax.lax.broadcasted_iota(jnp.int32, (R, D), 0)

                def head_row(h, ctx):
                    return jnp.where(row == h, acc_ref[u, :, pl.ds(
                        pl.multiple_of(h * D, D), D)], ctx)

                o_ref[n, u] = jax.lax.fori_loop(
                    0, heads, head_row, jnp.zeros((R, D), jnp.float32)) / l

            pl.when(b == last // block)(
                head_tiles if rep_p > 1 else unit_rows)
            return carry

        if units == 1:
            unit(0, 0)
        else:
            jax.lax.fori_loop(0, units, unit, 0)

    def turn(j, carry):
        @pl.when(j < n_items)
        def _():
            _by_rows(item_rows_ref[j], classes, functools.partial(start, j))

        i = j - ahead

        @pl.when(i >= 0)
        def _():
            _by_rows(item_rows_ref[i], classes, functools.partial(wait, i))
            score(i)

        return carry

    jax.lax.fori_loop(0, n_items + ahead, turn, 0)


def grouped_decode_attention(q, k_cache, v_cache, ts, work, *, n_head: int,
                             n_kv_head: int, scale: float, block: int,
                             tail: int, interpret=False):
    """The Pallas TPU kernel of read-what-is-live for grouped heads —
    and for ONE query head a K/V head at one fresh row — over
    unquantized sequence leaves whose heads a whole number of lane tiles
    holds (see the module docstring; :func:`step_read_sizes` is the
    rule).

    ``q`` ``[S, n_head * Dh]`` fp32 (one fresh row a slot, at ``ts``) or
    ``[S, K, n_head * Dh]`` (``K`` rows, row ``j`` at ``ts + j`` reading
    the positions ``<= ts + j``); ``k_cache``, ``v_cache`` ``[S, T,
    n_kv_head * Dh]`` bf16 or fp32, handed over AS THEY LIE and read only
    (:func:`append_rows` has written the step's rows); ``ts`` ``[S]``;
    ``work`` from :func:`decode_work_items` for the slots' LAST rows
    (:func:`last_fresh_row` of ``ts``; ``ts`` itself at one row) with
    ``block`` and ``tail`` (:func:`step_read_sizes`).  Returns ctx
    shaped like ``q``, fp32, zero for an idle slot.  ``q`` is scaled,
    then rounded to the leaves' dtype; the weights are rounded to it
    before they meet V (un-normalised, the fp32 sum of the unrounded ones
    divides at the end); every sum is fp32.  One jitted entry point for
    every call site (:func:`_kernel_call` says why): the leaves of one
    shape and ``K`` share one trace."""
    return _grouped_call()(
        work, ts, q, k_cache, v_cache, n_head=n_head, n_kv_head=n_kv_head,
        scale=float(scale), block=block, tail=tail, heads=_GROUPED_HEADS,
        ahead=_GROUPED_AHEAD, interpret=interpret)


def last_fresh_row(ts, fresh: int, seq_len: int):
    """The position of a slot's LAST fresh row that the leaves hold
    (``ts + fresh - 1``, the rung's last where that passes its end; idle
    slots stay ``< 0``): what the work list of a ``fresh``-row read is
    made for, and what :func:`kv_positions_read` rounds for the server's
    counter.  ``ts``: an integer array, numpy or jax."""
    if fresh == 1:
        return ts
    last, end = ts + fresh - 1, seq_len - 1
    last = last - (last > end) * (last - end)   # the lesser, in arithmetic
    return (ts >= 0) * (last + 1) - 1           # that numpy and jax share


@functools.lru_cache(maxsize=None)
def _grouped_call():
    import jax

    return jax.jit(_grouped, static_argnames=(
        "n_head", "n_kv_head", "scale", "block", "tail", "heads", "ahead",
        "interpret"))


def _head_rows(fresh: int, rep: int) -> int:
    """Query rows a K/V head takes of a unit of the grouped kernel: its
    ``fresh * rep`` (fresh row, query head) pairs, fresh row major, in
    whole fp32 tiles — or ONE where it has one (one query head a K/V
    head, one fresh row): the heads of a unit are then consecutive rows,
    padded to a tile once a unit, and the context comes back a row a
    head."""
    return 1 if fresh * rep == 1 else -(-fresh * rep // 8) * 8


def _unit_heads(n_kv_head: int, head_rows: int, heads: int = 0,
                pair: int = 1) -> int:
    """K/V heads the grouped kernel scores in one product: ``heads``
    where it is said and divides them (the tool's experiments), else as
    many — a divisor of ``n_kv_head`` — as keep a unit's query rows
    (``head_rows`` a head, :func:`_head_rows`) within
    :data:`_GROUPED_UNIT_ROWS`; in whole ``pair`` s
    (:func:`_heads_a_tile`), so that a unit's lanes are whole tiles.
    Eight 64-lane heads of 8 rows are ONE unit of 64 rows x 512 lanes:
    at ``[256,2048,512]`` 0.605 ms a call for 0.680 at four heads a unit
    and 0.976 at two, and blocks of 512 for 0.738 at 256 and 0.606 at
    1024 (chip run, PR 57, tools/time_grouped_decode.py --shape lfm2:
    the copies alone 0.571, the arithmetic alone 0.488; the lane-masked
    XLA form of the same read, over the whole rung: 1.78).
    One-row heads: every head of up to 64
    in ONE unit — at 30 heads of 128 the copies set the pace whatever the
    unit (0.637-0.654 ms a call at 5 / 6 / 10 / 15 / 30 heads a unit) and
    the arithmetic alone falls with the units (0.573 / 0.502 / 0.401 /
    0.366 / 0.329: a block's chain once a unit), so the widest unit
    leaves the most room behind the copies (chip run, PR 53,
    tools/time_grouped_decode.py)."""
    if heads and n_kv_head % heads == 0 and heads % pair == 0:
        return heads
    return max(h for h in range(pair, n_kv_head + 1, pair)
               if n_kv_head % h == 0
               and (h == pair or h * head_rows <= _GROUPED_UNIT_ROWS))


def _grouped(work, ts, q, k_cache, v_cache, *, n_head, n_kv_head, scale,
             block, tail, heads, ahead, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, T, Dkv = k_cache.shape
    G, D, rep = n_kv_head, Dkv // n_kv_head, n_head // n_kv_head
    K = 1 if q.ndim == 2 else q.shape[1]    # fresh rows a slot
    dt, f32, i32 = k_cache.dtype, jnp.float32, jnp.int32
    size = jnp.dtype(dt).itemsize
    rep_p = _head_rows(K, rep)
    pair = _heads_a_tile(D)             # heads a context tile goes out in
    if not pair or G % pair or (pair > 1 and rep_p == 1):
        raise ValueError("grouped_decode_attention: %d K/V heads of %d "
                         "lanes, %d query rows a head (step_read_sizes)"
                         % (G, D, K * rep))
    heads = _unit_heads(G, rep_p, heads, pair)          # K/V heads a unit
    units, L = G // heads, heads * D
    sub = 32 // size                    # rows of the leaves' sublane tile
    R = -(-heads * rep_p // sub) * sub
    qg = (q * scale).astype(dt).reshape(S, K, units, heads, rep, D)
    qg = jnp.moveaxis(qg, 1, 3).reshape(S, units, heads, K * rep, D)
    qg = jnp.pad(qg, ((0, 0),) * 3 + ((0, rep_p - K * rep), (0, 0)))
    if heads > 1 and rep_p > 1:
        # head h of a unit: its own D lanes of the unit's L (one-row
        # heads: the kernel lays a unit's [heads, D] rows out itself)
        own = np.eye(heads, dtype=bool)[:, None, :, None]
        qg = jnp.where(own, qg[:, :, :, :, None, :], jnp.zeros((), dt))
    qg = jnp.pad(qg.reshape(S, units, heads * rep_p, -1),
                 ((0, 0), (0, 0), (0, R - heads * rep_p), (0, 0)))
    # which fresh row each of a unit's query rows is (rows of padding:
    # the last), across a lane tile: an operand where there are several
    fresh_of = () if K == 1 else (jnp.broadcast_to(jnp.minimum(
        jnp.arange(R, dtype=i32) % rep_p // rep, K - 1)[:, None],
        (R, _HEAD_LANES)),)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    nbuf = ahead + 1
    # the context: a head's rows, or at one row a head a unit's rows
    out = ((S, G // pair, rep_p, pair * D) if rep_p > 1
           else (S, units, R, D))
    # bytes the kernel keeps in VMEM: q and the context whole, the slabs
    # being read and scored, a block's scores a few times over
    resident = (qg.size * size + 4 * int(np.prod(out))
                + 2 * nbuf * block * Dkv * size + 4 * 4 * R * block)
    ctx = pl.pallas_call(
        functools.partial(_grouped_kernel, block=block, tail=tail,
                          heads=heads, head_rows=rep_p, fresh=K,
                          pair=pair),
        out_shape=jax.ShapeDtypeStruct(out, f32),
        in_specs=[smem] * 5 + [vmem] * (1 + len(fresh_of)) + [hbm] * 2,
        out_specs=vmem,
        scratch_shapes=[
            pltpu.VMEM((nbuf, block, Dkv), dt),
            pltpu.VMEM((nbuf, block, Dkv), dt),
            pltpu.VMEM((units, R, _HEAD_LANES), f32),   # a unit's max,
            pltpu.VMEM((units, R, _HEAD_LANES), f32),   # sum
            pltpu.VMEM((units, R, L), f32),             # and weighted rows
            pltpu.SemaphoreType.DMA((2, nbuf)),
        ],
        compiler_params=(pltpu.CompilerParams(
            vmem_limit_bytes=min(100 << 20, 2 * resident))
            if resident > _VMEM_DEFAULT * 3 // 4 else None),
        name="grouped_decode_attention",
        interpret=interpret,
    )(*work, ts.astype(i32), qg, *fresh_of, k_cache, v_cache)
    if rep_p == 1:
        return ctx[:, :, :heads].reshape(q.shape)
    if pair > 1:    # a tile's heads side by side: each its own rows
        ctx = jnp.moveaxis(ctx.reshape(S, G // pair, rep_p, pair, D),
                           3, 2).reshape(S, G, rep_p, D)
    ctx = ctx[:, :, :K * rep].reshape(S, G, K, rep, D)
    return jnp.moveaxis(ctx, 2, 1).reshape(q.shape)


#: bytes of ONE ring leaf a grid step of the ring kernel holds (its
#: slots' rings whole; both leaves, twice for the copies in flight)
_RING_STEP_BYTES = 1 << 20
#: fresh rows the ring kernel scores on the vector unit, one after another
_RING_FRESH = 8


def ring_kernel_supported(kv, n_head: int, n_kv_head: int,
                          fresh: int) -> bool:
    """Whether :func:`ring_rows_decode_attention` takes ``fresh`` rows a
    slot over the ring leaves ``kv``: unquantized, heads of whole lane
    tiles, whole sublane tiles of ring rows, a slot's ring within a grid
    step's bytes, a few fresh rows."""
    if "k_scale" in kv or n_head % n_kv_head:
        return False
    _, rows, width = kv["k"].shape
    size = np.dtype(kv["k"].dtype).itemsize
    return ((width // n_kv_head) % _HEAD_LANES == 0
            and rows % (32 // size) == 0
            and rows * width * size <= _RING_STEP_BYTES
            and 1 < fresh <= _RING_FRESH)


def _ring_kernel(q_ref, ok_ref, kf_ref, vf_ref, k_ref, v_ref, o_ref, *,
                 fresh, rep, window):
    """A grid step's slots one after another, each K/V head from its own
    lanes of the slot's ring, which is ONE block a leaf: ``q_ref`` ``[n,
    G, R, Dh]`` (a head's ``fresh * rep`` query rows, fresh row major,
    padded to a sublane tile), ``ok_ref`` ``[n, R, rows]`` (1.0 where
    the query row may read the OLD ring's row), ``kf_ref`` / ``vf_ref``
    ``[n, fresh, G * Dh]`` float32 (the fresh rows as the leaf will hold
    them), ``k_ref`` / ``v_ref`` ``[n, rows, G * Dh]``, ``o_ref`` ``[n,
    G, R, Dh]`` float32.  The old ring's two products ride the matrix
    unit in the storage dtype; a fresh row is a multiply-and-sum over
    lanes on the vector unit (exact: the products of storage-dtype values
    in float32) under the static rule of which fresh rows a fresh row
    sees; ONE float32 softmax over both."""
    import jax
    import jax.numpy as jnp

    n, G, R, D = q_ref.shape
    dt, f32 = k_ref.dtype, jnp.float32
    # the fresh row each query row is (rows of padding: past the last)
    of = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) // rep
    sees = [(of >= j) & (of - j < window) for j in range(fresh)]

    def slot(i, carry):
        ok = ok_ref[i] > 0.0
        for g in range(G):
            lanes = slice(g * D, (g + 1) * D)
            q = q_ref[i, g]
            s_old = jnp.where(ok, jax.lax.dot_general(
                q, k_ref[i, :, lanes], (((1,), (1,)), ((), ())),
                preferred_element_type=f32), _MASK)
            qf = q.astype(f32)
            s_new = [jnp.where(sees[j], jnp.sum(
                qf * kf_ref[i, j:j + 1, lanes], axis=-1, keepdims=True),
                _MASK) for j in range(fresh)]
            top = functools.reduce(jnp.maximum, s_new,
                                   s_old.max(-1, keepdims=True))
            e_old = jnp.exp(s_old - top)
            e_new = [jnp.exp(s - top) for s in s_new]
            total = functools.reduce(jnp.add, e_new,
                                     e_old.sum(-1, keepdims=True))
            ctx = jnp.dot((e_old / total).astype(dt), v_ref[i, :, lanes],
                          preferred_element_type=f32)
            for j in range(fresh):
                ctx = ctx + ((e_new[j] / total).astype(dt).astype(f32)
                             * vf_ref[i, j:j + 1, lanes])
            o_ref[i, g] = ctx
        return carry

    jax.lax.fori_loop(0, n, slot, 0)


@functools.lru_cache(maxsize=None)
def _ring_call():
    import jax

    return jax.jit(_ring_read, static_argnames=("window", "interpret"))


def _ring_read(qg, fresh_k, fresh_v, k_ring, v_ring, old_ok, *, window,
               interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    S, G, rows_q, D = qg.shape
    K, (_, L, Dkv) = fresh_k.shape[1], k_ring.shape
    dt, f32 = k_ring.dtype, jnp.float32
    size = jnp.dtype(dt).itemsize
    rep = rows_q // K
    sub = 32 // size
    R = -(-rows_q // sub) * sub
    pad = ((0, 0), (0, R - rows_q), (0, 0))
    # slots a grid step: as many rings as its bytes hold
    n = max(m for m in range(1, S + 1)
            if S % m == 0
            and (m == 1 or m * L * Dkv * size <= _RING_STEP_BYTES))
    ok = jnp.pad(jnp.repeat(old_ok, rep, axis=1).astype(f32), pad)
    by_slot = lambda *tail: pl.BlockSpec(
        (n,) + tail, lambda i: (i,) + (0,) * len(tail))
    return pl.pallas_call(
        functools.partial(_ring_kernel, fresh=K, rep=rep, window=window),
        out_shape=jax.ShapeDtypeStruct((S, G, R, D), f32),
        grid=(S // n,),
        in_specs=[by_slot(G, R, D), by_slot(R, L), by_slot(K, Dkv),
                  by_slot(K, Dkv), by_slot(L, Dkv), by_slot(L, Dkv)],
        out_specs=by_slot(G, R, D),
        name="ring_rows_decode_attention",
        interpret=interpret,
    )(jnp.pad(qg, ((0, 0),) + pad), ok,
      fresh_k.astype(f32).reshape(S, K, Dkv),
      fresh_v.astype(f32).reshape(S, K, Dkv), k_ring, v_ring)[:, :, :rows_q]


def ring_rows_decode_attention(q, k_new, v_new, kv, ts, *, n_head: int,
                               n_kv_head: int, scale: float, window: int,
                               interpret=False):
    """The Pallas TPU kernel of ``K`` fresh rows a slot over RING leaves
    (:func:`ring_kernel_supported` is the rule): the contract, the
    layout and the mathematics of :func:`_ring_rows_attention`, whose
    append it shares — the old ring of a slot read as ONE block a leaf
    (``rows * n_kv_head * Dh``: 256 KB at ``k_exaone_236b_a23b``'s
    widths), the leaves handed over AS THEY LIE.

    Why a kernel where the plain XLA form copies nothing either: on the
    chip the compiler fetches each leaf ahead in slices and its read
    fusions take a 5-D view of that copy, so no instruction of the read
    bears the leaf's shape — and by that shape the device trace's
    readers find a window layer's work.  The kernel's call takes the two
    leaves themselves.  It costs 0.166 ms a call at those widths (chip
    run, PR 59: 404 GB/s, bound by the per-head products — a head's 128
    x 128 keys meet 16 query rows; the XLA form's fusions took 0.05 ms a
    layer and 0.015 of fetching).  One jitted entry point for every call
    site (:func:`_kernel_call` says why)."""
    RING_LOWERED.labels(form="rows").inc()
    ROWS_LOWERED.labels(leaf="ring").inc()

    def read(qg, fresh_k, fresh_v, kv, old_ok, new_ok):
        return _ring_call()(qg, fresh_k, fresh_v, kv["k"], kv["v"], old_ok,
                            window=int(window), interpret=interpret)

    return _ring_rows_attention(
        q, k_new, v_new, kv, ts, n_head=n_head, n_kv_head=n_kv_head,
        scale=scale, window=int(window), read=read)


def _gathered_block_attention(q, kv, ts, blocks, valid, *, n_head, n_kv_head,
                              scale, block):
    """The XLA form of the same read: a block gather, then the grouped
    masked softmax over what was gathered.  ctx ``[S, G, rep, Dh]``."""
    import jax
    import jax.numpy as jnp

    S, T, Dkv = kv["k"].shape
    G, D, rep = n_kv_head, Dkv // n_kv_head, n_head // n_kv_head
    dt = kv["k"].dtype
    qg = (q * scale).astype(dt).reshape(S, G, rep, D)
    # one gathered slice is a block's rows of ONE head's lanes, cut from
    # the leaf as it lies ([S, T, G * D]: no reshape of the leaf, which
    # would re-tile and copy the whole rung)
    at = jnp.stack(jnp.broadcast_arrays(
        jnp.arange(S)[:, None, None], blocks * block,
        (jnp.arange(G) * D)[None, :, None]), axis=-1)       # [S, G, B, 3]
    dn = jax.lax.GatherDimensionNumbers(
        offset_dims=(3, 4), collapsed_slice_dims=(0,),
        start_index_map=(0, 1, 2))
    # [S, G, B * block, D]: the blocks' rows as one run of key rows
    kg, vg = (jax.lax.gather(kv[name], at, dn, (1, block, D),
                             mode="promise_in_bounds").reshape(
                                 S, G, -1, D) for name in ("k", "v"))
    pos = blocks[..., None] * block + jnp.arange(block)
    ok = (valid[..., None] & (pos <= ts[:, None, None, None])).reshape(
        S, G, 1, -1)
    scores = jnp.einsum("sgrd,sgkd->sgrk", qg, kg,
                        preferred_element_type=jnp.float32)
    w = jax.nn.softmax(jnp.where(ok, scores, -1e9), axis=-1)
    return jnp.einsum("sgrk,sgkd->sgrd", w.astype(dt), vg,
                      preferred_element_type=jnp.float32)


def grouped_block_decode_attention(q, kv, ts, blocks, valid, dense, *,
                                   n_head: int, n_kv_head: int,
                                   scale: float, block: int,
                                   dense_len: int, shared_runs=()):
    """Read the blocks named for each row.

    ``q`` ``[S, n_head * Dh]`` fp32, one query row per slot at position
    ``ts`` (its K/V row already appended: :func:`append_rows`);
    ``blocks`` ``[S, n_kv_head, B]`` int32 and ``valid`` the same shape:
    the blocks slot ``s`` reads for K/V head ``g`` (distinct where
    valid; ``rep`` query heads share a list), of which positions ``<=
    ts`` count.  A slot with ``dense`` set reads every position ``<=
    ts`` instead; such a slot is below ``dense_len``, so that branch
    reads the first ``dense_len`` positions of the rung and runs only in
    a step that has one (``lax.cond``).  Products in the storage dtype,
    fp32 accumulation and softmax.  Returns ctx ``[S, n_head * Dh]``
    fp32, zero for an idle slot.  No branch reads the whole rung: ``B *
    block`` positions a slot and head, however long ``T`` is.

    ``shared_runs`` (static) is what the caller KNOWS of its lists and
    may declare: ``((first entry, entries), ...)``, each a stretch of
    entries that every head's list holds alike and that, where valid,
    name consecutive blocks in rising order with no invalid entry
    between two valid ones (a rule's forced blocks: the first ones, the
    window).  It changes nothing that is read — the lists say that — only
    how the kernel fetches it
    (:func:`block_sparse_decode_attention`); the XLA form takes the
    lists as they are.

    Two implementations of the named read, chosen here from what can be
    observed, as :func:`make_decode_attention` chooses: the Pallas kernel
    (:func:`block_sparse_decode_attention`) on a TPU for shapes it
    lowers for (:func:`block_kernel_supported`), else the XLA form (a
    block gather: the CPU path and the kernel's parity reference —
    XLA:TPU runs that gather as a serial loop of slices, 64x off the
    roofline at 12k slices a layer)."""
    import jax
    import jax.numpy as jnp

    S, T, Dkv = kv["k"].shape
    G, D, rep = n_kv_head, Dkv // n_kv_head, n_head // n_kv_head
    dt = kv["k"].dtype
    live = ts >= 0
    named = dict(n_head=n_head, n_kv_head=n_kv_head, scale=scale,
                 block=block)
    if (jax.default_backend() == "tpu"
            and block_kernel_supported(kv, n_head, n_kv_head, block)):
        BLOCK_SPARSE_LOWERED.labels(path="kernel").inc()
        ctx = block_sparse_decode_attention(
            q, kv["k"], kv["v"], ts, blocks, valid,
            shared_runs=shared_runs, **named)
    else:
        BLOCK_SPARSE_LOWERED.labels(path="xla").inc()
        ctx = _gathered_block_attention(q, kv, ts, blocks, valid, **named)
    qg = (q * scale).astype(dt).reshape(S, G, rep, D)

    n_dense = min(int(dense_len), T)
    use_dense = dense & live

    def dense_read():
        seen = (jnp.arange(n_dense)[None, :] <= ts[:, None])[:, None]
        out = []
        for g in range(G):      # a head's lanes: a tile-aligned slice
            kd, vd = (kv[name][:, :n_dense, g * D:(g + 1) * D]
                      for name in ("k", "v"))
            sc = jnp.einsum("srd,std->srt", qg[:, g], kd,
                            preferred_element_type=jnp.float32)
            wd = jax.nn.softmax(jnp.where(seen, sc, -1e9), axis=-1)
            out.append(jnp.einsum("srt,std->srd", wd.astype(dt), vd,
                                  preferred_element_type=jnp.float32))
        return jnp.stack(out, axis=1)

    ctx_dense = jax.lax.cond(jnp.any(use_dense), dense_read,
                             lambda: jnp.zeros_like(ctx))
    ctx = jnp.where(use_dense[:, None, None, None], ctx_dense, ctx)
    return jnp.where(live[:, None], ctx.reshape(S, n_head * D), 0.0)


def make_decode_attention(ts, kv, *, n_head: int, n_kv_head: int,
                          scale: float, window=None):
    """``attend(q, k_new, v_new, kv) -> (ctx, kv)`` for one step at
    positions ``ts``, shared by its layers of one leaf kind and length
    (``kv``: any one of those layers' leaves, all alike; a step whose
    layers hold sequence leaves AND ring leaves makes one ``attend`` for
    each).  ``window``: the leaves are RING leaves of that window (what
    the builder allocated them as), read by the XLA form — but ``K``
    fresh rows a slot on a TPU over leaves the ring kernel takes
    (:func:`ring_kernel_supported`), which go through
    :func:`ring_rows_decode_attention`.  The one place
    that chooses, from what it can observe (the backend, the leaves'
    dtype and shape, the head grouping, ``q.ndim``): a kernel when one
    exists for what the step is — the default backend a TPU, unquantized
    leaves, and either one query head per K/V head over fp32 leaves of a
    shape :func:`ragged_decode_attention` lowers for and one fresh row
    per slot, or heads that a whole number of lane tiles holds
    (:func:`step_read_sizes`: :func:`grouped_decode_attention` — grouped
    heads over bf16 / fp32 leaves, a head whole lane tiles or an even
    number of heads of 64 lanes, one fresh row or ``K``; one query head
    per K/V head over bf16 leaves of whole-lane-tile heads, one fresh
    row) — and an XLA form otherwise: on a TPU, for one row over
    unquantized leaves of grouped heads narrower than a lane tile that
    the kernel does not take (an odd number of them, a rung its block
    does not divide), or over bf16 leaves of one query head per K/V head
    that it does not (64-lane heads, such a rung), the one that reads
    the leaves as they lie (:func:`lane_masked_decode_attention`), else
    :func:`grouped_masked_decode_attention` (int8 leaves, ``K`` rows
    where no kernel takes them, every CPU run).  A grouped-head step
    over sequence leaves counts itself in
    ``decode_attention_grouped_lowered_total{path}``, a ``K``-row one
    also in ``decode_attention_rows_lowered_total{leaf}``, a step of one
    query head per K/V head over sequence leaves in
    ``decode_attention_ungrouped_lowered_total{path}``."""
    import jax
    import jax.numpy as jnp

    _, seq_len, width = kv["k"].shape
    xla = functools.partial(grouped_masked_decode_attention, ts=ts,
                            n_head=n_head, n_kv_head=n_kv_head, scale=scale)
    # one fresh row over unquantized leaves, read as they lie
    lane = functools.partial(lane_masked_decode_attention, ts=ts,
                             n_head=n_head, n_kv_head=n_kv_head, scale=scale)
    tpu = jax.default_backend() == "tpu" and "k_scale" not in kv
    if window is not None:
        ring = functools.partial(xla, window=int(window))

        def attend(q, k_new, v_new, kv):
            if not (tpu and q.ndim == 3 and ring_kernel_supported(
                    kv, n_head, n_kv_head, q.shape[1])):
                return ring(q, k_new, v_new, kv)
            return ring_rows_decode_attention(
                q, k_new, v_new, kv, ts, n_head=n_head, n_kv_head=n_kv_head,
                scale=scale, window=int(window))

        return attend
    sizes = step_read_sizes(
        seq_len, width, kv["k"].dtype, n_head=n_head,
        n_kv_head=n_kv_head) if tpu else None
    works = {}      # the work list of a K-row read, made once a K

    def kernel(q, k_new, v_new, kv):
        fresh = 1 if q.ndim == 2 else q.shape[1]
        if fresh not in works:
            works[fresh] = decode_work_items(
                last_fresh_row(ts, fresh, seq_len), seq_len, *sizes)
        if fresh > 1:
            ROWS_LOWERED.labels(leaf="sequence").inc()
        kv = append_rows(kv, k_new, v_new, ts)
        return grouped_decode_attention(
            q, kv["k"], kv["v"], ts, works[fresh], n_head=n_head,
            n_kv_head=n_kv_head, scale=scale, block=sizes[0],
            tail=sizes[1]), kv

    if n_kv_head < n_head:
        one_row = xla
        if sizes is None and tpu and (width // n_kv_head) % _HEAD_LANES:
            # narrower than a lane tile and not the kernel's (an odd
            # number of heads, a rung its block does not divide): a view
            # of the leaf by heads would be a copy of the rung
            # (lane_masked_decode_attention)
            one_row = lane

        def attend(q, k_new, v_new, kv):
            GROUPED_LOWERED.labels(
                path="xla" if sizes is None else "kernel").inc()
            if sizes is not None:
                return kernel(q, k_new, v_new, kv)
            return (one_row if q.ndim == 2 else xla)(q, k_new, v_new, kv)

        return attend
    ragged = (tpu and kv["k"].dtype == jnp.float32 and n_kv_head == n_head
              and kernel_supported(seq_len, width, n_head))
    one_row = xla
    if ragged:
        block = kv_read_block(seq_len)
        work = decode_work_items(ts, seq_len, block)
    elif sizes is not None:
        # bf16 leaves of whole-lane-tile heads: the grouped kernel's read
        # with a head's ONE query row a row of a unit
        one_row = kernel
    elif tpu and kv["k"].dtype == jnp.bfloat16:
        # no kernel's shape (heads that are not whole lane tiles, a rung
        # the block does not divide).  With ONE query row a K/V head the
        # compiler takes the score product of the per-head view off the
        # matrix unit and first copies each leaf to float32 in another
        # layout (2 x a leaf of temporaries a leaf and step): read the
        # leaves as they lie
        one_row = lane

    def attend(q, k_new, v_new, kv):
        one = q.ndim == 2       # K fresh rows per slot: no kernel yet
        UNGROUPED_LOWERED.labels(
            path="kernel" if one and (ragged or one_row is kernel)
            else "xla").inc()
        if not (ragged and one):
            return (one_row if one else xla)(q, k_new, v_new, kv)
        ctx, k, v = ragged_decode_attention(
            q, k_new, v_new, kv["k"], kv["v"], ts, work,
            n_head=n_head, scale=scale, block=block)
        return ctx, {"k": k, "v": v}

    return attend
