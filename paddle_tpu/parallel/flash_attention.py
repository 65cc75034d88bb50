"""Pallas TPU flash attention (FlashAttention-2 style).

The hot op of the transformer family (SURVEY.md section 7: "pallas kernels
for the hot ops"). Both directions are K-blocked with online softmax: the
score matrix never exists at full [tq, tk] size in any memory space, so
VMEM use is O(hb * block^2) and HBM traffic is O(t) regardless of context
length — the property the long-context/ring-attention path builds on.

The BHTD kernels tile a call by the shape they see (``_pick_tile``): a grid
step works on hb heads x bq query rows x bk key rows, (1, hb, bq|bk, dh)
blocks of the native [b, h, t, dh] layout over the grid (b, h/hb, tq/bq,
tk/bk). Where all heads fit the VMEM caps at blocks of 256 they share a
step (hb = h): a per-head grid at a short sequence is dominated by per-step
DMA/setup overhead (measured 331us per 44us-ideal forward at t=256), and
batching the heads amortizes it 8x. Where they do not (16 heads of 128, 8
of 64), the heads go onto the grid and the blocks grow to 512 (256 or 128
where 512 does not divide the sequence): batching the heads left a long
sequence blocks of 128 x 128, 128 FLOPs per byte fetched against the v5e's
ridge of 240.

- Forward: k-blocks inner; running (m, l, acc) in VMEM scratch across
  the k-block loop; emits the output AND the logsumexp. Its tile is its
  own (``bhtd_fwd_tile``): the blocks above and, where the heads are on
  the grid, TWO query heads a step, a chain each in the step's body
  (a call 11-22% shorter: PERF.md section 6, PR 74), the two heads
  of one key head's group over ONE fetched block of K, of V and of a
  selection's words (``_row_specs``), under the forward's own VMEM
  count (``_fwd_vmem_bytes``); the backward keeps one head a step. The logsumexp
  crosses HBM in the layout its consumer reads: [b, h, 1, tq] float32
  ROWS (``bhtd_stats_form``), four bytes a position, cut by the ONE
  backward call into [1, bq] blocks along the lanes. A [.., tq, 1]
  float32 COLUMN is tiled (8, 128) on the chip, so each row's one value
  owns a lane tile of 512 bytes: a q block of 512 rows writes 256 KB
  of it (as much as the step's K and V blocks together), the residual
  lives to the backward at 128 times its bytes (201 MB a layer at 48
  heads x 8192), and XLA copies it back into rows in front of the
  backward. So the column is written only where a q block cannot be
  cut from a row (a caller's ``q_block`` of 64).
  ``flash_attention_fwd`` RETURNS [b, h, tq, 1] either way: a reshape,
  which folds against the backward's inside one jit.
- Backward: recompute p = exp(s - lse) per block (no stored attention),
  using the standard delta = rowsum(do * o) reduction. ONE kernel
  (``attn.bhtd.bwd``, q-blocks inner) computes a live block's scores,
  exp and dp once and adds to all three gradients, 5 matmuls; what a
  k-row's scratch cannot gather (dq; dk and dv where a group of query
  heads shares them) stays resident in VMEM for a head and is written
  once, so nothing gradient-sized but dq, dk, dv reaches HBM. Where
  ``bhtd_bwd_form`` says a call does not fit that kernel (resident rows
  over its VMEM cap, heads batched in a step, dropout) the pair runs:
  dq in one kernel (k-blocks inner), dk/dv in a second (q-blocks
  inner), 7 matmuls and two walks. Exposed as
  ``flash_attention_bwd`` so the framework's sdpa_grad op can consume the
  forward's saved (out, lse) instead of re-running the forward kernel
  (XLA cannot CSE custom calls, so a vjp-style recompute would execute).
- ``causal``: a step whose block lies above the diagonal computes nothing
  and fetches nothing (its index maps repeat the row's nearest live
  block, and Pallas copies only a block whose index changed).
- ``window`` (with ``causal``): a query also FORGETS, it sees the last
  ``window`` positions (p - s < window). The BHTD kernels' inner grid
  axis is then as long as the band is wide in blocks, not as the
  sequence: step r of a row works on the r-th block of the row's band,
  dead steps (the first rows' bands are shorter) repeat the row's last
  live block, and the two-sided mask runs only on the blocks the
  diagonal or the band's far edge crosses.
- ``block_diffusion=B`` (exclusive with ``causal`` and ``window``): the
  row is two halves of t / 2, a NOISED copy and the CLEAN copy of the
  same L positions cut into blocks of B, under block diffusion's
  training mask (Arriola et al. 2025, arXiv:2503.09573; ``bd_visible``
  is the rule's one dense copy): a noised block sees itself, both
  ways, and the clean blocks before it; the clean half is block-causal;
  no clean query sees a noised key. The mask is no function of p - s,
  so the walk is its own (``_bd_k_step``, ``_bd_q_step``): a q-row's
  steps are its own diagonal block and THEN the clean half's blocks
  from the first, a clean k-row's steps the noised q-blocks from its
  own on and then the clean ones, a noised k-row's its own q-block
  alone; a clean q-row never fetches a noised block. The edge blocks
  (a q-row's own diagonal block; a noised q-row's clean block of its
  own positions) are masked at block granularity and worked on WHOLE,
  in the forward and in the one backward call alike
  (``bhtd_edge_tile``: None). A block-masked call runs in
  ``attn.bhtd.fwd`` and the ONE ``attn.bhtd.bwd``, or not in kernels at
  all: where its tile is not square, does not cut a half into whole
  blocks, is not whole blocks of B, or the backward would be the pair
  (``_fused_fits``), ``bhtd_tile`` gives it none and it runs as the
  dense composition, which the dispatch counter says.
- An EDGE block is one that the diagonal or a band's far edge crosses;
  every other live block is plain: all visible, no mask, in every
  kernel and under plain ``causal`` too (``_when_live``). The ONE
  backward call walks an edge block in sub-tiles (``_edge_tile``: 256 on
  a side where the block is 512 x 512, from the block's shape alone;
  none where the block is too small or not square): each sub-tile dead,
  plain or edge by the block's own predicates at its own corners
  (``_band_live``, ``_on_edge``), the dead ones dropped and the live
  ones of a query sub-tile gathered into one slab of the block as it
  lies in VMEM (``_edge_slabs``). What the mask does to a block goes by
  how far its first query is behind its first key, and a call's edge
  blocks have two or three such kinds (the diagonal's, the far edge's),
  so the classification is made when the call is traced and a kind's
  slabs are ONE straight line of code: no grid step, DMA, operand or
  scratch is added, only the order of the float32 sums inside an edge
  block changes. The forward and the split pair work on an edge block
  whole (the forward's time does not go by its pairs: PERF.md section 6,
  PR 58). ``bhtd_edge_tile`` says which sub-tile a backward call takes
  (the dispatch counter's ``edge`` label), ``bhtd_pairs(tq, tk, tile,
  causal, window) -> (computed, live)`` the score pairs a head's steps
  compute there and those the mask lets through (laguna's band of one
  block: 6.09M for 4.06M; 8.13M on whole blocks, ``form=None``).
- Attention dropout runs inside the kernels via the TPU PRNG: the mask for
  score block (b, jq, jk) is regenerated from a hash of (seed, b, jq, jk)
  in every kernel (and of the head group, where hb < h), so forward and
  backward see identical masks and nothing is stored.

- Queries and keys in TWO parts (``q_pe`` [b, h, t, r], ``k_pe`` [b, hp,
  t, r] beside q and k: latent attention's rotary features, the keys'
  ONE head shared by all query heads): ``attn.bhtd.fwd`` and the ONE
  ``attn.bhtd.bwd`` take the parts as operands of their own where
  ``bhtd_parts`` says so, at the tile and the form of the call with
  heads of dh + r, and read a shared head of k_pe through its index map
  (``_row_specs``: query head // (h / hp), as a group's K and V). No
  wide q or k exists in HBM, no copy of the shared head, and the
  backward writes dq_pe and a dk_pe a query head beside dq and dk. The
  forward's scores are two products in one float32 sum; the backward
  puts a step's blocks together in VMEM and is the wide call's step.

- A SELECTION (``selected`` [b, tq / 32, tk] int32 beside ``causal``, a
  bit a pair, with its ``live`` block table: a learned sparse
  attention's top-k, which keys each query reads, all heads alike): the
  mask is DATA, so it is an operand: ``attn.bhtd.fwd`` and the ONE
  ``attn.bhtd.bwd`` read it in (bq / 32, bk) blocks of words through the
  bias operand's slot, unpack a block in VMEM (``dsa_score.hit_rows``;
  ``_biased`` masks) and walk the causal triangle; a block the live
  table says holds no selected pair computes nothing and fetches
  nothing: the table rides behind the seed pair in the scalar-prefetch
  operand as the block each step FETCHES (``_selection``, ``_fetched``:
  its own where it is live, else a live neighbour's, which Pallas does
  not copy again), the index maps read it (``_step_blocks``) and a step
  is live where it fetches its own (``_chosen_live``).
  ``bhtd_selected`` says which calls; elsewhere the dense composition
  masks by it. A call without a selection lowers as it did.

``bias`` is additive [b, 1|h, 1|tq, tk] mask plumbing, NOT a trainable
input: its cotangent is zeros on the Pallas path (computing it would
materialize a t x t gradient). Use the dense composition for a learnable
additive bias.

Off-TPU, or for a shape no kernel family takes, attention runs as the
dense jnp composition. The choice is a pure function of backend and
shape (``bthd_family`` / ``bhtd_family``); the sdpa op records it per
lowered call in ``pt_attention_dispatch_total`` (ops/attention_ops.py),
so a dense fallback is never silent.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.parallel import dsa_score

DEFAULT_Q_BLOCK = 256
DEFAULT_K_BLOCK = 256
# Blocks of a tile whose heads went onto the grid (see _pick_tile).
_GRID_HEADS_BLOCK = 512
_NEG_INF = -1e30

# The two caps a tile (hb heads x bq query rows x bk key rows) is held
# to. Both count what ONE grid step keeps in VMEM, so they scale with the
# heads in the block, not with the heads of the call.
#
# Soft cap on the f32 score block (hb * bq * bk * 4B). Mosaic sums ALL of
# a kernel's score-sized temps on its ~16MB scoped-vmem stack (the dkv
# kernel holds ~6 of them plus casts and scratch), so the per-block cap
# must stay well under limit/6. 1.5MB admits 8 heads of 128 x 256 (with
# a [*, tq, tk] bias at t=1024 and beyond) and one head of 512 x 512.
# It is the BACKWARD's count, and the tile it gives (_pick_tile) is the
# backward's and the blocks of both passes; how many heads a FORWARD
# step takes at those blocks is the forward's own count
# (``bhtd_fwd_tile``, ``_fwd_vmem_bytes``: about three score-sized temps,
# under a limit the call sets itself).
_SCORE_VMEM_BYTES = 3 * 2**19
# Soft cap on what the dk/dv kernel keeps of (hb, bk, dh) besides its
# score temps: k and v (bf16, double-buffered), dk and dv out (the same)
# and two f32 accumulators, 24 bytes an element. 16 heads of 128 with a
# 256-row k block are 12.6MB of them and the kernel asked for 17.1MB of
# Mosaic's 16MB (compiled for a v5e, PR 28); 8 heads of 64 hold 3.1MB,
# one head of 128 with 512 rows 1.6MB.
_KV_VMEM_BYTES = 2**23

# Test hook: run the Pallas kernels in interpreter mode on CPU so the
# blocked online-softmax path itself is exercised by the pytest suite
# (the reference-composition fallback would otherwise shadow it off-TPU).
_INTERPRET = False


def kernels_enabled() -> bool:
    """The Pallas kernels need a TPU backend (tests reach them on CPU
    through the interpreter)."""
    return jax.default_backend() == "tpu" or bool(_INTERPRET)


def _block_seed(seed, *keys):
    """Mix (seed, batch row, [head group,] q-block, k-block) into one
    scalar for the per-core PRNG (the multi-operand prng_seed form
    doesn't lower on all backends). int32 wraparound is the hash."""
    s = seed
    for x in keys:
        s = (s * jnp.int32(1000003)) ^ jnp.int32(x)
    return s


def _dropout_mask(p_keep: float, shape):
    """Per-block keep mask from the already-seeded TPU PRNG, scaled by
    1/p_keep (inverted dropout)."""
    bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    thresh = jnp.uint32(int(p_keep * float(2**32 - 1)))
    return (bits < thresh).astype(jnp.float32) * (1.0 / p_keep)


def _tile_fits(hb, bq, bk, dh, dv=None):
    # (12 bytes an element of k and dk, 12 of v and dv: 24 * dh where
    # the values are as wide as the keys)
    return (12 * hb * bk * (dh + (dv or dh)) <= _KV_VMEM_BYTES
            and 4 * hb * bq * bk <= _SCORE_VMEM_BYTES)


def _pick_tile(h, tq, tk, q_block, k_block, dh, group=1, dv=None):
    """-> (hb, bq, bk): heads, query rows and key rows of one grid step,
    from what the call shows (h, dh, tq, tk; ``dv`` where the values
    and the output are not as wide as the queries and keys: latent
    attention's 192 over 128) and the two VMEM caps.
    ``q_block`` / ``k_block``: None for the kernels' own choice, a number
    for an upper bound the caller sets (tests reach several blocks at a
    small t that way).

    Where all h heads fit at blocks of 256 they stay in one step: a
    short sequence has few steps, and the per-step cost is what head
    batching was built against. Where a cap refuses that, the heads go
    onto the grid before a block shrinks, and the blocks grow to 512
    instead: a step's FLOPs per byte fetched go with its q rows, and at
    h * dh = 2048 a step over all heads could keep 128 of them. A block
    that does not divide its sequence halves until it does (not under
    128), and a step then keeps as many heads as the caps admit: hb4
    256 x 256 at t = 768, all 16 heads of 128 at 128 x 128 at t = 640.

    ``group`` > 1 (grouped-query attention: that many query heads read
    one key/value head): the heads always go onto the grid, one a step,
    so that a step's K and V block is one head's and the index maps pick
    it (``q head // group``): the group's K and V are read from HBM where
    they lie, never copied ``group`` times."""
    bq = min(q_block or DEFAULT_Q_BLOCK, tq)
    bk = min(k_block or DEFAULT_K_BLOCK, tk)
    if group == 1 and _tile_fits(h, bq, bk, dh, dv):
        return h, bq, bk
    bq = min(q_block or _GRID_HEADS_BLOCK, tq)
    bk = min(k_block or _GRID_HEADS_BLOCK, tk)
    # (a ring's quarter of 3072 is 768: 512 does not divide it, 256 does)
    while tq % bq and bq > 128:
        bq //= 2
    while tk % bk and bk > 128:
        bk //= 2
    while not _tile_fits(1, bq, bk, dh, dv) and bk > 128:
        bk //= 2
    while not _tile_fits(1, bq, bk, dh, dv) and bq > 64:
        bq //= 2
    if group > 1:
        return 1, bq, bk
    hb = max(d for d in range(1, h + 1)
             if h % d == 0 and (d == 1 or _tile_fits(d, bq, bk, dh, dv)))
    return hb, bq, bk


def bhtd_tile(h, tq, tk, q_block=None, k_block=None, *, dh, group=1,
              dv=None, block_diffusion=None, itemsize=2):
    """-> (hb, bq, bk), the tile the K-blocked [b, h, t, dh] kernels take
    for a call of this shape, or None where they do not take it (no TPU
    backend, or blocks that do not tile both sequence lengths) and it
    runs as the dense composition. The one place that decides either:
    the kernels' entry points, ``bhtd_family`` and the dispatch counter's
    ``tile`` label all read it. ``block_diffusion=B``: the call is
    block-masked, and is taken where its blocks are square, cut a half
    of the row into whole blocks and are whole blocks of B, and its
    backward is the ONE call (``_fused_fits``; ``itemsize``: of q, which
    that count reads)."""
    hb, bq, bk = tile = _pick_tile(h, tq, tk, q_block, k_block, dh, group,
                                   dv)
    if not (kernels_enabled() and tq % bq == 0 and tk % bk == 0):
        return None
    if block_diffusion and not (
            bq == bk and tq == tk and (tq // 2) % bq == 0
            and bq % block_diffusion == 0
            and _fused_fits(tile, tq, tk, dh, dv or dh, group, itemsize)):
        return None
    return tile


def tile_label(tile) -> str:
    """A tile as the dispatch counter's ``tile`` label has it:
    "hb1 bq512 bk512" ("" for None)."""
    return "hb%d bq%d bk%d" % tile if tile else ""


# A side of the sub-tiles an edge block is walked in (_when_live).
_EDGE_SUB = 256


def _edge_tile(bq, bk):
    """-> (sq, sk), the sub-tiles a (bq, bk) block that the diagonal or a
    band's far edge crosses is walked in, from the block's shape alone,
    or None where the block is worked on whole: ``_EDGE_SUB`` on a side
    where the block is square and that cuts its side in whole parts
    (whole lane tiles of the score block and of the [1, bq] statistics'
    rows). Square, because each kind of edge block (_edge_slabs' d) is a
    branch of the kernel's body and a call of square blocks has at most
    three: the diagonal's and the far edge's one or two."""
    if bq != bk or bq <= _EDGE_SUB or bq % _EDGE_SUB:
        return None
    return _EDGE_SUB, _EDGE_SUB


def bhtd_edge_tile(tile, causal, form="fused"):
    """-> (sq, sk) or None: the sub-tiles in which the backward call of
    this tile walks its edge blocks. The one place that decides:
    ``_fused_bwd``, ``bhtd_pairs`` and the dispatch counter's ``edge``
    label read it. No edge without ``causal`` (a block-masked call's
    edge blocks are not the diagonal's and are worked on whole: its
    ``causal`` is False); the forward (``form`` None) and the split pair
    work on an edge block whole."""
    if tile is None or not causal or form != "fused":
        return None
    return _edge_tile(tile[1], tile[2])


def edge_label(sub) -> str:
    """A sub-tile as the dispatch counter's ``edge`` label has it:
    "256x256" ("" for None)."""
    return "%dx%d" % sub if sub else ""


def bhtd_stats_form(tile, tq):
    """-> "rows" | "column" (None for no tile): the layout in which
    ``attn.bhtd.fwd`` writes a call's logsumexp to HBM. "rows":
    [b, h, 1, tq] float32, the positions along the 128 lanes, four bytes
    a row, and what ``attn.bhtd.bwd`` and ``attn.bhtd.bwd_dkv`` read.
    "column": [b, h, tq, 1], which the chip tiles (8, 128), so that
    every row's one value owns a lane tile of 512 bytes: 128 times the
    bytes, written by the forward, kept to the backward, and copied back
    into rows by XLA in front of it. Rows where the q block can be cut
    from a row (whole lane tiles of it, or all of it: the test
    ``bhtd_bwd_form`` puts to a fused call); the column where it cannot
    (a caller's ``q_block`` of 64). The one place that decides:
    ``flash_attention_fwd``, the dispatch counter's ``stats`` label and
    the tests read it."""
    if tile is None:
        return None
    bq = tile[1]
    return "rows" if bq % 128 == 0 or bq == tq else "column"


def bhtd_family(h, tq, tk, q_block=None, k_block=None, *, dh,
                group=1, dv=None, block_diffusion=None, itemsize=2) -> str:
    """"bhtd" (the K-blocked [b, h, t, dh] kernels) when the picked
    blocks tile both sequence lengths (and, block-masked, the call is
    one ``bhtd_tile`` takes), else "dense"."""
    tile = bhtd_tile(h, tq, tk, q_block, k_block, dh=dh, group=group,
                     dv=dv, block_diffusion=block_diffusion,
                     itemsize=itemsize)
    return "bhtd" if tile else "dense"


# What the fused backward call may keep in VMEM: its resident rows, its
# blocks and its score temps (_bwd_vmem_bytes). Half of a v5e core's 128
# MiB: the call raises Mosaic's scoped limit (16 MiB by default) to a
# quarter over its own count, and the half left is room for what the
# count does not see (Mosaic's spills and relayout buffers, the
# pipeline's semaphores). The longest calls of the cells count 59 MB
# (28 / 4 heads of 128 x 16,384 and 16 / 2 of 256 x 8192); twice their
# rows under a shared key/value head is the pair's.
_BWD_VMEM_CAP_BYTES = 64 * 2**20


def _bwd_vmem_bytes(tq, tk, dh, dv, group, bq, bk, itemsize):
    """What ``attn.bhtd.bwd`` keeps in VMEM at this call: the float32
    accumulators (dq's resident rows; dk's and dv's, or a block of each
    where no group shares them; counted in whole lane tiles: a float32
    row under 128 features takes 128), the gradients' output blocks and the
    operands' blocks, double-buffered, and eight score-sized float32
    temps."""
    rk = tk if group > 1 else bk
    grads = tq * dh + rk * (dh + dv)
    # (a float32 row under 128 features still takes a lane tile: heads
    # of 64 x 16,384 rows, Granite-4.0-H's, allocate 49 MB where the
    # count by features says 32)
    lanes = lambda d: -(-d // 128) * 128
    accs = tq * lanes(dh) + rk * (lanes(dh) + lanes(dv))
    blocks = bq * (dh + dv) + bk * (dh + dv)
    return (4 * accs + 2 * itemsize * (grads + blocks) + 2 * 4 * 2 * bq
            + 8 * 4 * bq * bk)


def _bwd_vmem_limit(*call):
    """Mosaic's scoped limit for the fused call: what it keeps and a
    quarter more, not under the default of 16 MiB."""
    return max(16 * 2**20, _bwd_vmem_bytes(*call) * 5 // 4)


def _fused_fits(tile, tq, tk, dh, dv, group, itemsize, p_drop=0.0):
    """Does the ONE backward call take this tile (``bhtd_bwd_form`` says
    why each condition)?"""
    hb, bq, bk = tile
    return not (hb > 1 or p_drop > 0.0 or bhtd_stats_form(tile, tq) != "rows"
                or _bwd_vmem_bytes(tq, tk, dh, dv, group, bq, bk, itemsize)
                > _BWD_VMEM_CAP_BYTES)


def bhtd_bwd_form(h, tq, tk, q_block=None, k_block=None, *, dh, group=1,
                  dv=None, itemsize=2, p_drop=0.0, block_diffusion=None):
    """-> "fused" (ONE call, ``attn.bhtd.bwd``: a live block's scores,
    exp and dp computed once, dq, dk and dv taken from them), "split"
    (the pair ``attn.bhtd.bwd_dq`` + ``attn.bhtd.bwd_dkv``) or None (no
    tile: the dense composition), from what the call shows. The one
    place that decides: ``flash_attention_bwd``, the dispatch counter's
    ``form`` label and the tests read it.

    Fused keeps the gradients that a step's scratch cannot hold RESIDENT
    in VMEM for a whole head, so it takes the calls whose rows fit
    ``_BWD_VMEM_CAP_BYTES``; one head a step (a tile that batches heads
    is a short sequence: few steps, nothing resident to win); blocks
    that cut lse and delta from [1, tq] rows (a multiple of the 128
    lanes, or the whole row); no dropout (the mask stream is keyed by
    the split grids' head group). A bias does not matter: both forms
    carry it. A block-masked call (``block_diffusion``) has a tile only
    where it is fused (``bhtd_tile``)."""
    dv = dv or dh
    tile = bhtd_tile(h, tq, tk, q_block, k_block, dh=dh, group=group, dv=dv,
                     block_diffusion=block_diffusion, itemsize=itemsize)
    if tile is None:
        return None
    if _fused_fits(tile, tq, tk, dh, dv, group, itemsize, p_drop):
        return "fused"
    return "split"


def bhtd_parts(h, tq, tk, q_block=None, k_block=None, *, dh, r, hp,
               group=1, dv=None, itemsize=2, plain=True):
    """Do ``attn.bhtd.fwd`` and the ONE ``attn.bhtd.bwd`` take this call's
    queries and keys in TWO parts each, Q | QPe of dh | r features a
    head and K | KPe with KPe's hp heads shared by h / hp query heads
    each (latent attention: 128 | 64, ONE rotary key head), as operands
    of their own, so that nobody assembles a wide q or copies a shared
    head? Where the call is ``plain`` (the caller's word: no bias, no
    dropout, no window, no block mask; causal or not), hp divides h, K
    has a head a query head (``group`` 1: the backward gathers dk_pe,
    a query head's own, in the k-row's scratch that gathers dk) and the
    call at dh + r features has a tile whose backward is the ONE call
    (one head a step). The one place that decides: the entry
    points, the sdpa op's fallback (which assembles q and k where this
    says no) and the dispatch counter's ``parts`` label read it."""
    return bool(plain and h % hp == 0 and group == 1 and bhtd_bwd_form(
        h, tq, tk, q_block, k_block, dh=dh + r, dv=dv or dh,
        itemsize=itemsize) == "fused")


# Query heads a step of ``attn.bhtd.fwd`` works on where the call's heads
# are on the grid (``bhtd_fwd_tile``), the side of the blocks from which
# it does (what the chip timed: every cell's 512 x 512; a caller's
# smaller block, a test's, keeps one head a step, and tests reach two
# at blocks of 128 by setting it), and what such a step may keep in
# VMEM by the forward's own count. A quarter of a v5e core's 128 MiB:
# two heads of 512 x 512 count 9 to 13 MB, so the cap refuses only what
# nobody measured.
_FWD_HEADS = 2
_FWD_PAIR_BLOCK = 512
_FWD_VMEM_CAP_BYTES = 32 * 2**20


def _fwd_vmem_bytes(hq, hkv, bq, bk, dh, dv, itemsize, stats="rows",
                    selected=False, bias=None):
    """What a step of ``attn.bhtd.fwd`` keeps in VMEM: ``hq`` query
    heads' blocks of q and out and of the logsumexp, double-buffered;
    the three statistics' scratch (m and l along 128 lanes, the float32
    accumulator); ``hkv`` heads' blocks of K and V, double-buffered;
    three float32 score blocks and p in the call's dtype; under a
    selection its words, double-buffered, and their unpacked int32
    block, ONE a step whatever the heads; a float ``bias`` (its
    [b, 1 | h, 1 | tq, tk] shape) its block, double-buffered, as
    float32. ``dh``: the whole head's width where q and k come in two
    parts (the shared rotary key head is counted a head a K block:
    over, by a few KB)."""
    lse = 4 * bq if stats == "rows" else 512 * bq
    rows = hq * (2 * itemsize * bq * (dh + dv) + 2 * lse
                 + 4 * bq * (2 * 128 + dv))
    keys = 2 * itemsize * hkv * bk * (dh + dv)
    scores = hq * bq * bk * (3 * 4 + itemsize)
    chosen = (2 * 4 * (bq // 32) * bk + 4 * bq * bk) if selected else 0
    added = 0 if bias is None else 2 * 4 * bk * (
        (hq if bias[1] > 1 else 1) * (bq if bias[2] > 1 else 1))
    return rows + keys + scores + chosen + added


def _fwd_vmem_limit(*step, **kw):
    """Mosaic's scoped limit for the forward call: what a step keeps and
    a quarter more, or None where that is within Mosaic's default of 16
    MiB and the call asks for nothing (one head of 512 x 512 counts 5
    MB: the call lowers as before there was a count)."""
    limit = _fwd_vmem_bytes(*step, **kw) * 5 // 4
    return limit if limit > 16 * 2**20 else None


def _kv_blocks(hq, group):
    """Heads of K and of V that a forward step of ``hq`` query heads
    holds: ONE where they are of one key head's group, a head each
    where a key head serves one query head."""
    return hq if group == 1 else 1


def bhtd_fwd_tile(h, tq, tk, q_block=None, k_block=None, *, dh, group=1,
                  dv=None, block_diffusion=None, itemsize=2, p_drop=0.0,
                  bias=None, pe_group=None, selected=False):
    """-> (hq, bq, bk), the tile ``attn.bhtd.fwd`` takes for a call of
    this shape (None where ``bhtd_tile`` gives none): the blocks are
    ``bhtd_tile``'s, which the backward walks too (``bhtd_pairs`` counts
    one walk), the query heads of a step the forward's own. Where the
    heads are on the grid (``bhtd_tile``: one a step) a forward step
    takes ``_FWD_HEADS`` = 2 of them, so that a step holds two heads'
    chains and the two heads of one key head's group read ONE fetched
    block of K, of V and of a selection's words (``_row_specs``); a key
    head a query head (``group`` 1), the step holds both heads' K and V
    blocks. The step's count (``_fwd_vmem_bytes``) is the forward's own,
    under ``_FWD_VMEM_CAP_BYTES``, and the call sets Mosaic's limit from
    it (``_fwd_vmem_limit``); ``_SCORE_VMEM_BYTES`` is the backward's.

    One head a step stays where the two do not pair: an odd number of
    heads; an odd ``group`` over 1 (28 heads on 4: heads 6 and 7 read
    two key heads; a whole group of seven a step is 39 MB by the count,
    over the cap; a K and a V block for each of two heads measured
    -11.8% a call and is not taken: PERF.md section 7 (41a));
    ``pe_group`` (query heads a head of KPe, where q and k come in two
    parts) neither even nor 1; blocks under ``_FWD_PAIR_BLOCK`` on a
    side (nobody timed them); ``p_drop`` > 0 (a block's
    mask is keyed by the step's head GROUP, ``_seed_step``, and the
    backward draws it again at one head a step: two heads a step would
    train on another mask than they are differentiated under).
    ``bias``: the shape of a float bias, which the count reads. A pure
    function of what the call shows, as ``bhtd_tile`` is: the entry
    point, the dispatch counter's ``tile`` label of a ``pass="fwd"`` row
    and the tests read it."""
    tile = bhtd_tile(h, tq, tk, q_block, k_block, dh=dh, group=group, dv=dv,
                     block_diffusion=block_diffusion, itemsize=itemsize)
    if tile is None or tile[0] != 1 or p_drop > 0.0:
        return tile
    _, bq, bk = tile
    hq, dv = _FWD_HEADS, dv or dh
    pairs = (h % hq == 0 and (group == 1 or group % hq == 0)
             and (pe_group in (None, 1) or pe_group % hq == 0)
             and min(bq, bk) >= _FWD_PAIR_BLOCK)
    if not pairs or _fwd_vmem_bytes(
            hq, _kv_blocks(hq, group), bq, bk, dh, dv, itemsize,
            bhtd_stats_form(tile, tq), selected, bias) > _FWD_VMEM_CAP_BYTES:
        return tile
    return hq, bq, bk


# ---------------------------------------------------------------------------
# kernels — refs are blocks of the native [b, h, t, dh] layout over the
# grid (batch row, head group, q-block, k-block); index 0 drops the
# leading size-1 batch-block dim, so shapes below are q (hb, bq, dh) /
# k, v (hb, bk, dh) / bias (1|hb, 1|bq, bk) / lse (hb, 1, bq) rows or,
# where a q block cannot be cut from a row, (hb, bq, 1) columns
# (bhtd_stats_form).
# ---------------------------------------------------------------------------


def _causal_mask(s, j, kk, bq, bk, transposed=False, window=None,
                 at=(0, 0)):
    """Mask future positions inside score block (hb, bq, bk), or one
    head's (bq, bk), for q-block
    j / k-block kk (``transposed``: block is (hb, bk, bq)); with a
    ``window`` also the positions it has forgotten (p - s >= window).
    ``at``: where ``s`` starts inside the block, (query row, key row),
    where it is a slab of it."""
    q0, k0 = j * bq + at[0], kk * bk + at[1]
    rows, cols = s.ndim - 2, s.ndim - 1     # (one head's block: [bq, bk])
    if transposed:
        k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, rows) + k0
        q_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, cols) + q0
    else:
        q_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, rows) + q0
        k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, cols) + k0
    seen = q_pos >= k_pos
    if window is not None:
        seen = jnp.logical_and(seen, q_pos - k_pos < window)
    return jnp.where(seen, s, _NEG_INF)


def _causal_live(j, kk, bq, bk):
    """Does block (q=j, k=kk) contain ANY unmasked element? A block fully
    above the diagonal is a dead step: its compute is skipped here, and
    its operands are not fetched either, because the index maps hand a
    dead step the block of its row's nearest live step (_live_k,
    _live_q) and Pallas copies nothing when a block index repeats."""
    return kk * bk <= (j + 1) * bq - 1


def _live_k(j, kk, bq, bk):
    """The k-block step (j, kk) of a q-row reads under ``causal``: its
    own while it is live, then the row's last live one. The k axis is the
    inner one of the forward and dq grids, and a row's dead steps are its
    tail."""
    return jnp.minimum(kk, ((j + 1) * bq - 1) // bk)


def _live_q(j, kk, bq, bk):
    """The q-block step (j, kk) of a k-row reads under ``causal``: the
    row's first live one until the walk reaches it, then its own. The q
    axis is the inner one of the dk/dv grid, and a row's dead steps are
    its head. (A k-row past the last query has no live step: the caller
    bounds the result by the last q-block.)"""
    return jnp.maximum(j, (kk * bk) // bq)


# A window's band. visible(p, s) = s <= p and p - s < window, so block
# (j, kk) holds a visible pair iff kk * bk <= (j + 1) * bq - 1 (the
# diagonal's side, _causal_live) and j * bq <= (kk + 1) * bk + window - 2
# (the far edge's side): the two conditions are independent, a q-row's
# live k-blocks are _first_k .. _live_k's bound and a k-row's live
# q-blocks (kk * bk) // bq .. _last_q. The inner grid axis walks the
# band: step r of a row is the r-th block from the row's first.


def _first_k(j, bq, bk, window):
    """The first k-block a q-row's window reaches back to."""
    return jnp.maximum(j * bq - window + 1, 0) // bk


def _last_q(kk, bq, bk, window):
    """The last q-block that still remembers a k-row's positions."""
    return ((kk + 1) * bk + window - 2) // bq


def _band_steps(n_rows, first, last):
    """Blocks of the widest row's band: the inner grid axis's length
    (``first`` / ``last`` take a row's index as a Python int)."""
    return max(int(last(r)) - int(first(r)) + 1 for r in range(n_rows))


def _on_edge(j, kk, bq, bk, window):
    """Does the diagonal or the band's far edge cross block (j, kk):
    is some pair of it in the future, or forgotten? Every other live
    block is all visible and takes no mask. (Grid indices in a kernel,
    Python ints where a call's geometry is reckoned.)"""
    diagonal = (kk + 1) * bk - 1 > j * bq
    if window is None:
        return diagonal
    return diagonal | ((j + 1) * bq - 1 - kk * bk >= window)


def _band_live(j, kk, bq, bk, window):
    """Does block (j, kk) hold ANY visible pair: is it neither all in the
    future nor, with a ``window``, all forgotten?"""
    live = _causal_live(j, kk, bq, bk)
    if window is None:
        return live
    return live & (j <= _last_q(kk, bq, bk, window))


# Block diffusion's mask. ``bd`` = (B, L): the row is a noised and a
# clean copy of L positions in blocks of B, and with P = p mod L, S = s
# mod L, bp = P // B, bs = S // B a pair is visible where
#
#   p <  L, s <  L:  bp == bs     a noised block sees itself, both ways
#   p <  L, s >= L:  bs <  bp     and the clean blocks before it
#   p >= L, s >= L:  bs <= bp     the clean half is block-causal
#   p >= L, s <  L:  never
#
# Kernel blocks are square (bq), cut a half into nh = L // bq of them and
# hold whole blocks of B (bhtd_tile), so q-block j of the noised half has
# live k-blocks j (its diagonal block: an edge), then nh .. nh + j of the
# clean half (the last, its own positions' clean copy, an edge: bs < bp),
# and q-block nh + j of the clean half has nh .. nh + j (the last, its
# diagonal block, an edge: bs <= bp). Both walks visit a row's own
# diagonal block FIRST, so a row's running maximum is finite before it
# meets a block in which some of its queries see nothing.


def bd_visible(t, block):
    """[t, t] bool: the rule above over a row of t = 2 L positions, the
    ONE dense copy of it (the composition's mask, the tests')."""
    i = jnp.arange(t)
    noised, blk = i < t // 2, (i % (t // 2)) // block
    bp, bs = blk[:, None], blk[None, :]
    return jnp.where(noised[:, None],
                     jnp.where(noised[None, :], bp == bs, bs < bp),
                     jnp.logical_and(~noised[None, :], bs <= bp))


def _halves(block_diffusion, causal, window, tq, tk):
    """``bd`` = (B, L) of a block-masked call, None for any other."""
    if not block_diffusion:
        return None
    block = int(block_diffusion)
    if (causal or window is not None or tq != tk or tq % 2
            or block < 1 or (tq // 2) % block):
        raise ValueError(
            f"attention: block_diffusion={block_diffusion} needs "
            f"self-attention over a row of two halves of whole blocks, "
            f"and neither causal nor a window (causal={causal}, "
            f"window={window}, tq={tq}, tk={tk})")
    return block, tq // 2


def _bd_k_step(j, r, bq, bd):
    """Step r of q-row j, k-blocks inner -> (the k-block it works on,
    is it live, is it an edge): the row's own block (r = 0), then the
    clean half's from its first."""
    nh = bd[1] // bq
    # clean blocks the row sees: a noised row j its own positions' too
    seen = jnp.where(j < nh, j + 1, j - nh)
    kk = jnp.where(r == 0, j, nh + r - 1)
    return (kk, jnp.logical_or(r == 0, r - 1 < seen),
            jnp.logical_or(r == 0, r - 1 == j))


def _bd_k_fetch(j, r, bq, bd):
    """The k-block step r of q-row j READS: a dead step the row's last
    live one."""
    nh = bd[1] // bq
    last = jnp.where(j < nh, j, j - nh - 1)
    return jnp.where(r == 0, j,
                     nh + jnp.clip(r - 1, 0, jnp.maximum(last, 0)))


def _bd_q_step(kk, r, bq, bd):
    """Step r of k-row kk, q-blocks inner -> (the q-block it works on,
    is it live, is it an edge). A noised k-row is seen by its own
    q-block alone (step 0). Clean k-row nh + c: by the noised q-blocks
    c .. nh - 1 (the first an edge), then by the clean ones nh + c .. 2
    nh - 1 (the first, its diagonal block, an edge)."""
    nh = bd[1] // bq
    c = kk - nh
    noised = kk < nh
    j = jnp.where(noised, kk, jnp.where(r < nh - c, c + r, r + 2 * c))
    return (j, jnp.where(noised, r == 0, r < 2 * (nh - c)),
            jnp.logical_or(r == 0, r == nh - c))


def _bd_q_fetch(kk, r, bq, bd):
    """The q-block step r of k-row kk READS: a dead step the row's last
    live one."""
    return jnp.minimum(_bd_q_step(kk, r, bq, bd)[0], 2 * (bd[1] // bq) - 1)


def _block_of(x, block):
    """x // block of int32 positions (a shift where it is one)."""
    if block & (block - 1) == 0:
        return jax.lax.shift_right_logical(
            x, jnp.int32(block.bit_length() - 1))
    return x // block


def _bd_mask(s, j, kk, bq, bd, transposed=False):
    """Mask edge block (q=j, k=kk)'s scores [.., bq, bk] (``transposed``:
    [.., bk, bq]). An edge block's queries and keys start at the same
    position of their halves, so a pair's blocks compare by the rows'
    and the columns' own indices: a noised query sees its own block of
    the noised keys, the blocks before its own of the clean ones; a
    clean query its own and those before."""
    nh = bd[1] // bq
    qa, ka = ((s.ndim - 1, s.ndim - 2) if transposed
              else (s.ndim - 2, s.ndim - 1))
    ahead = (_block_of(jax.lax.broadcasted_iota(jnp.int32, s.shape, qa),
                       bd[0])
             - _block_of(jax.lax.broadcasted_iota(jnp.int32, s.shape, ka),
                         bd[0]))                 # bp - bs
    q_noised, k_noised = j < nh, kk < nh
    least = jnp.where(
        jnp.logical_and(q_noised, jnp.logical_not(k_noised)), 1, 0)
    most = jnp.where(jnp.logical_and(q_noised, k_noised), 0, bq)
    seen = jnp.logical_and(ahead >= least, ahead <= most)
    return jnp.where(seen, s, _NEG_INF)


@functools.lru_cache(maxsize=None)
def _edge_slabs(tq, tk, bq, bk, sub, window):
    """How a call's edge blocks are walked in sub-tiles of ``sub`` = (sq,
    sk): -> {d: [((q0, rows, k0, cols), masked), ..]}. Whether a pair is
    visible goes by p - s alone, so what the mask does to block (j, kk)
    goes by d = j * bq - kk * bk alone, how far the block's first query
    is behind its first key, and a call's edge blocks have a few d (the
    diagonal's 0 and the far edge's one or two, where bq == bk). For each
    the block's sub-tiles are classified by the block's own predicates
    at their own corners (``_band_live``, ``_on_edge`` in units of the
    sub-tile): the dead ones dropped, the live ones of a query sub-tile
    gathered into ONE slab, rows q0 .. q0 + rows by key rows k0 .. k0 +
    cols of the block (a row's live sub-tiles are contiguous: the band
    is convex; a slab's keys are the rows the backward's transposed
    matmuls stream), ``masked`` where one of them is an edge. The slabs
    of a block are straight-line code, in rising order. (Reckoned once a
    geometry: ``_fused_bwd`` and ``bhtd_pairs`` both ask; nobody writes
    into the answer.)"""
    (sq, sk), slabs = sub, {}
    na, nc = bq // sq, bk // sk
    for j in range(tq // bq):
        for kk in range(tk // bk):
            if j * bq - kk * bk in slabs or not (
                    _band_live(j, kk, bq, bk, window)
                    and _on_edge(j, kk, bq, bk, window)):
                continue
            parts = []
            for a in range(na):
                # (key sub-tile, is it an edge) of query sub-tile a's
                # live ones
                live = [(c, _on_edge(j * na + a, kk * nc + c, sq, sk, window))
                        for c in range(nc)
                        if _band_live(j * na + a, kk * nc + c, sq, sk,
                                      window)]
                if live:
                    first, n = live[0][0], len(live)
                    assert [c for c, _ in live] == list(range(first,
                                                              first + n))
                    parts.append(((a * sq, sq, first * sk, n * sk),
                                  any(edge for _, edge in live)))
            slabs[j * bq - kk * bk] = parts
    return slabs


def _when_live(compute, live, j, kk, bq, bk, window, slabs=None, edge=None):
    """Run a masked step on block (j, kk), ``live`` or dead: not at all
    where dead, ``compute(masked=False)`` on a plain block (all of it
    visible), ``compute(masked=True)`` on an edge block (the diagonal or
    the band's far edge crosses it; ``edge``: the caller's own answer,
    a block-masked call's). With ``slabs`` (_edge_slabs) an
    edge block is walked in the slabs of its live sub-tiles instead, in
    VMEM as the block lies there: ``compute(masked, at=(q0, rows, k0,
    cols))``, one straight line a block; its dead sub-tiles cost
    nothing."""
    if edge is None:
        edge = _on_edge(j, kk, bq, bk, window)
    pl.when(jnp.logical_and(live, jnp.logical_not(edge)))(
        functools.partial(compute, masked=False))
    if not slabs:
        pl.when(jnp.logical_and(live, edge))(
            functools.partial(compute, masked=True))
        return
    for d, parts in slabs.items():
        def _walk(parts=parts):
            for at, masked in parts:
                compute(masked=masked, at=at)
        pl.when(jnp.logical_and(live, j * bq - kk * bk == d))(_walk)


def _slab(at, bq, bk):
    """-> (rows of the q block, rows of the k block, where they start):
    all of both for ``at`` None."""
    q0, rows, k0, cols = at or (0, bq, 0, bk)
    return slice(q0, q0 + rows), slice(k0, k0 + cols), (q0, k0)


def bhtd_pairs(tq, tk, tile, causal, window=None, form="fused",
               block_diffusion=None):
    """-> (computed, live): the score pairs (query position, key
    position) that the steps of ONE head compute in the backward call of
    this tile (``form`` None: in the forward, or in the split pair, whose
    edge blocks are whole), and those of them the mask lets through. A
    pure function of the call's geometry, by the kernels' own
    predicates: a dead block or sub-tile is not computed, a plain or an
    edge one is computed whole. ``block_diffusion``: the block-masked
    call's, L^2 + B L live pairs, the same blocks either pass (its edge
    blocks are whole)."""
    _, bq, bk = tile
    bd = _halves(block_diffusion, causal, window, tq, tk)
    if bd is not None:
        nq, (block, half) = tq // bq, bd
        rows = jnp.arange(nq)[:, None]
        if form is None:    # the forward's walk: nh + 1 steps a q-row
            steps = jnp.arange(half // bq + 1)[None, :]
            live = _bd_k_step(rows, steps, bq, bd)[1]
        else:               # the backward's: nq steps a k-row
            live = _bd_q_step(rows, jnp.arange(nq)[None, :], bq, bd)[1]
        return int(live.sum()) * bq * bk, half * half + block * half
    if not causal:
        return tq * tk, tq * tk
    window = _band(window, causal, tq, tk)
    live = sum(max(min(p + 1, tk) - max(p - (window or tq) + 1, 0), 0)
               for p in range(tq))
    sub = bhtd_edge_tile(tile, causal, form)
    slabs = _edge_slabs(tq, tk, bq, bk, sub, window) if sub else {}
    walked = {d: sum(rows * cols for (_, rows, _, cols), _ in parts)
              for d, parts in slabs.items()}
    computed = sum(
        walked.get(j * bq - kk * bk, bq * bk)
        if _on_edge(j, kk, bq, bk, window) else bq * bk
        for j in range(tq // bq) for kk in range(tk // bk)
        if _band_live(j, kk, bq, bk, window))
    return computed, live


def _seed_step(seed_ref, ng, j, kk):
    """Seed the PRNG for score block (batch row, head group, j, kk).
    With all heads in one group the key is (row, j, kk), the stream the
    kernels have always drawn; with heads on the grid the group joins,
    or every group would draw the same mask."""
    keys = (pl.program_id(0) + seed_ref[1],)
    if ng > 1:
        keys += (pl.program_id(1),)
    pltpu.prng_seed(_block_seed(seed_ref[0], *keys, j, kk))


def _lanes(x, n):
    """``x`` (.., 128), every lane of a row the same value, as (.., n)."""
    if n <= 128:
        return x[..., :n]
    if n % 128 == 0:
        return pltpu.repeat(x, n // 128, axis=x.ndim - 1)
    return jnp.broadcast_to(x[..., :1], x.shape[:-1] + (n,))


def _biased(s, bias, transposed=False):
    """Scores ``s`` [.., bq, bk] behind the step's block of the bias
    operand: a float bias is added; a SELECTION's block (``_unpacked``:
    int32, nonzero where the query reads the key) masks.
    ``transposed``: ``s`` is [bk, bq] and the block comes [bq, bk] (as
    32-bit values: the chip transposes nothing narrower)."""
    if bias.dtype == jnp.int32:
        return jnp.where((bias.T if transposed else bias) != 0, s, _NEG_INF)
    bias = bias.astype(jnp.float32)
    return s + (bias.T if transposed else bias)


def _unpacked(bias, first=0, rows=None):
    """A block of the bias operand as ``_biased`` takes it: a
    selection's (bq / 32, bk) words as rows ``first`` .. ``first +
    rows`` of the block's pairs (all of them: None), int32 and nonzero
    where the bit is set (``dsa_score.hit_rows``); a float block as it
    is."""
    if bias.dtype != jnp.int32:
        return bias
    return dsa_score.hit_rows(bias.reshape(bias.shape[-2:]), first, rows)


def _table_at(seed_ref, live, i, j, kk):
    """Entry (batch row i, q-block j, k-block kk) of a selection's table
    behind the seed pair in the scalar-prefetch operand (``_selection``;
    ``live`` = its (nq, nk)): the block the step fetches along its
    call's inner axis."""
    nq, nk = live
    return seed_ref[2 + (i * nq + j) * nk + kk]


def _chosen_live(seed_ref, live, j, kk, own):
    """Does block (j, kk) of this batch row hold a selected pair? Where
    its step fetches its ``own`` block of the inner axis (kk in the
    forward, j in the backward: ``_fetched``)."""
    return _table_at(seed_ref, live, pl.program_id(0), j, kk) == own


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, nk, ng, p_drop,
                causal=False, window=None, bd=None, pe_refs=None,
                live=None, chain=None):
    # r: the inner axis's step, nk of them; kk the k-block it works on
    # (``pe_refs``: blocks of QPe and KPe where q and k come in two parts,
    # _call_parts). ``chain``: heads a chain. None: the step's heads are
    # ONE batched chain (a tile that batches a short row's heads,
    # _pick_tile). 1 (the forward's own tile, bhtd_fwd_tile): a chain a
    # head, one behind the other, each the one-head step's operations in
    # its order on its operands, against its own head of K and V or the
    # ONE head the step's heads share. (Timed against the heads batched,
    # their rows through one product, and the heads' scores made first:
    # 18.3 ms a call for 20.0-20.1 at [1, 32 / 4, 16384, 128] under a
    # selection on a v5e, PR 74; benchmarks/attn_fwd_candidates.py.)
    j, r = pl.program_id(2), pl.program_id(3)
    hq, bq, bk = q_ref.shape[1], q_ref.shape[2], k_ref.shape[2]
    chain = chain or hq
    if bd is not None:
        kk, bd_live, bd_edge = _bd_k_step(j, r, bq, bd)
    else:
        kk = r if window is None else _first_k(j, bq, bk, window) + r

    @pl.when(r == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def heads(ref, first):
        # a chain's heads of an operand's block. One batched chain: the
        # block, [hq, ., .]. A chain a head: ONE head's [., .], the
        # step's ``first``-th where the block has a head each, the
        # block's one (a group's K or V, the rotary key head) where the
        # step's heads share it
        if chain == hq:
            return ref[0]
        return ref[0, first if ref.shape[1] == hq else 0]

    def _compute(masked=False):
        for first in range(0, hq, chain):
            _chain(masked, first, slice(None) if chain == hq else first)

    def _chain(masked, first, own):
        # (``own``: the chain's rows of the statistics' scratch. The
        # products' axes from the end: a batched chain's blocks lead
        # with their heads, one head's do not)
        q = heads(q_ref, first)
        k = heads(k_ref, first)
        v = heads(v_ref, first)
        d, batch = q.ndim - 1, (tuple(range(q.ndim - 2)),) * 2

        def scores(a, b):
            return jax.lax.dot_general(a, b, (((d,), (d,)), batch),
                                       preferred_element_type=jnp.float32)

        s = scores(q, k)
        if pe_refs is not None:     # q k^T + q_pe k_pe^T, one float32 sum
            s = s + scores(heads(pe_refs[0], first),
                           heads(pe_refs[1], first))
        s = s * scale
        if bias_ref is not None:
            # (a selection's words are unpacked for each chain again: the
            # block's MB of int32 kept from one chain to the next cost
            # 3.5% of a call, 18.93 ms for 18.28 at keye's, PR 74)
            s = _biased(s, _unpacked(heads(bias_ref, first)))
        if masked and bd is not None:
            s = _bd_mask(s, j, kk, bq, bd)
        elif masked:
            s = _causal_mask(s, j, kk, bq, bk, window=window)

        # m and l live replicated along the 128 lanes of their scratch:
        # a row's max and sum are broadcast once each, and the score
        # block and the accumulator read whole vregs of them (sliced to
        # one lane and broadcast again every step, the forward took 2.95
        # ms where it takes 1.65: OLMoE's shape on a v5e, PR 29)
        m_prev = m_scr[own]
        l_prev = l_scr[own]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, bk))
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)

        if p_drop > 0.0:    # (one chain: bhtd_fwd_tile)
            _seed_step(seed_ref, ng, j, kk)
            p = p * _dropout_mask(1.0 - p_drop, p.shape)

        acc_scr[own] = (acc_scr[own] * _lanes(corr, acc_scr.shape[2])
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((d,), (d - 1,)), batch),
                            preferred_element_type=jnp.float32))
        m_scr[own] = m_new
        l_scr[own] = l_new

    if bd is not None:
        _when_live(_compute, bd_live, j, kk, bq, bk, None, edge=bd_edge)
    elif causal:
        # (a band's steps start at the q-row's first live k-block; under
        # a selection a block in which nothing is chosen is dead too)
        is_live = _causal_live(j, kk, bq, bk)
        if live is not None:
            is_live = jnp.logical_and(
                is_live, _chosen_live(seed_ref, live, j, kk, kk))
        _when_live(_compute, is_live, j, kk, bq, bk, window)
    else:
        _compute()

    @pl.when(r == nk - 1)
    def _finish():
        l = l_scr[:, :, :1]
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        if lse_ref.shape[3] == 1:       # the column (bhtd_stats_form)
            lse_ref[0] = m_scr[:, :, :1] + jnp.log(l)
        else:
            # rows: every lane of the scratch holds its row's value, so
            # a head's block transposed has the [1, bq] row in every
            # sublane
            lse = m_scr[:] + jnp.log(l_scr[:])
            for i in range(lse.shape[0]):
                lse_ref[0, i] = lse[i].T[:1]


def _dq_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
               delta_ref, dq_ref, dq_scr, *, scale, nk, ng, p_drop,
               causal=False, window=None):
    j, r = pl.program_id(2), pl.program_id(3)   # as in _fwd_kernel
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    kk = r if window is None else _first_k(j, bq, bk, window) + r

    @pl.when(r == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute(masked=False):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]        # (hb, bq, 1) f32
        delta = delta_ref[0]    # (hb, bq, 1) f32

        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale
        if bias_ref is not None:
            s = s + bias_ref[0].astype(jnp.float32)
        if masked:
            s = _causal_mask(s, j, kk, bq, bk, window=window)
        p = jnp.exp(s - lse)  # post-softmax probabilities, recomputed

        dp = jax.lax.dot_general(
            do, v, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        if p_drop > 0.0:
            _seed_step(seed_ref, ng, j, kk)
            dp = dp * _dropout_mask(1.0 - p_drop, dp.shape)
        ds = p * (dp - delta) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )

    if causal:
        _when_live(_compute, _causal_live(j, kk, bq, bk), j, kk, bq, bk,
                   window)
    else:
        _compute()

    @pl.when(r == nk - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                scale, nq, ng, p_drop, causal=False, group=1, window=None,
                last_q=None):
    kk, jq = pl.program_id(2), pl.program_id(3)
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    walk, steps = jq, nq   # the inner axis: the q-blocks of one head
    if group > 1:
        # grouped-query attention: the inner axis walks the group's
        # query heads, each over its q-blocks, and dk, dv gather all
        steps, jq = group * nq, walk % nq
    if window is not None:
        # nq steps a head over the k-row's band, from its first q-block
        # (``last_q``: the sequence's last one)
        jq = (kk * bk) // bq + jq

    @pl.when(walk == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute(masked=False):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse_t = lse_ref[0]      # (hb, 1, bq) f32: rows, as s_t wants them
        delta_t = delta_ref[0]
        if lse_t.shape[1] != 1:  # (hb, bq, 1) columns: flash_attention_bwd
            lse_t = jnp.transpose(lse_t, (0, 2, 1))
            delta_t = jnp.transpose(delta_t, (0, 2, 1))

        # Work in the transposed orientation: s_t (hb, bk, bq)
        s_t = jax.lax.dot_general(
            k, q, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale
        if bias_ref is not None:
            s_t = s_t + jnp.transpose(bias_ref[0].astype(jnp.float32),
                                      (0, 2, 1))
        if masked:
            s_t = _causal_mask(s_t, jq, kk, bq, bk, transposed=True,
                               window=window)
        p_t = jnp.exp(s_t - lse_t)

        if p_drop > 0.0:
            # Same (row, group, q-block, k-block) stream as the forward,
            # generated in the forward's (hb, bq, bk) orientation then
            # transposed.
            _seed_step(seed_ref, ng, jq, kk)
            drop_t = jnp.transpose(
                _dropout_mask(
                    1.0 - p_drop,
                    (p_t.shape[0], p_t.shape[2], p_t.shape[1])),
                (0, 2, 1),
            )
            pd_t = p_t * drop_t
        else:
            pd_t = p_t

        dv_scr[:] += jax.lax.dot_general(
            pd_t.astype(do.dtype), do, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        dp_t = jax.lax.dot_general(
            v, do, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        if p_drop > 0.0:
            dp_t = dp_t * drop_t
        ds_t = p_t * (dp_t - delta_t) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds_t.astype(q.dtype), q, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # (a band's steps start at the k-row's first live q-block)
        live = (_causal_live(jq, kk, bq, bk) if window is None else
                jq <= jnp.minimum(_last_q(kk, bq, bk, window), last_q))
        _when_live(_compute, live, jq, kk, bq, bk, window)
    else:
        _compute()

    @pl.when(walk == steps - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_block(q, k, v, do, lse, delta, bias, scale, mask):
    """One (q-block, k-block) pair's part of the three gradients, its
    scores, their exp and dp computed ONCE: q, do [bq, .], k, v [bk, .],
    lse and delta [1, bq] rows -> (dq [bq, dh], dk [bk, dh], dv [bk, dv])
    in float32. The block is worked on transposed, s_t [bk, bq], as
    _dkv_kernel does: the statistics lie along the lanes (no padded
    column), dv and dk are plain products and only dq takes a
    transposed left operand. ``mask``: None, or what an edge block's
    scores go through."""
    f32 = jnp.float32
    nt = (((1,), (1,)), ((), ()))       # a b^T
    s_t = jax.lax.dot_general(k, q, nt, preferred_element_type=f32) * scale
    if bias is not None:
        s_t = _biased(s_t, bias, transposed=True)
    if mask is not None:
        s_t = mask(s_t)
    p_t = jnp.exp(s_t - lse)
    dp_t = jax.lax.dot_general(v, do, nt, preferred_element_type=f32)
    ds_t = (p_t * (dp_t - delta) * scale).astype(q.dtype)
    dv = jnp.dot(p_t.astype(do.dtype), do, preferred_element_type=f32)
    dk = jnp.dot(ds_t, q, preferred_element_type=f32)
    dq = jax.lax.dot_general(ds_t, k, (((0,), (0,)), ((), ())),
                             preferred_element_type=f32)
    return dq, dk, dv


def _block_rows(acc, idx, rows, inside=slice(None)):
    """Block ``idx`` of an accumulator's rows: of the one block it holds,
    or of a resident one. ``inside``: a slab's rows of that block alone
    (a static slice)."""
    if acc.shape[0] == rows:
        return inside
    first, n = inside.start or 0, (inside.stop or rows) - (inside.start or 0)
    return pl.ds(pl.multiple_of(idx * rows + first, math.gcd(rows, first)),
                 n)


def _each_block(acc, rows, body):
    """``body(rows of block c)`` for every block of an accumulator (a
    loop: a resident one is thousands of vregs)."""
    def step(c, carry):
        body(_block_rows(acc, c, rows))
        return carry
    jax.lax.fori_loop(0, acc.shape[0] // rows, step, 0)


def _bwd_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                delta_ref, *grads, scale, nq, nk, group, causal=False,
                window=None, last_q=None, slabs=None, bd=None, pe_refs=None,
                live=None):
    """attn.bhtd.bwd: grid (batch row, key/value head, member of its
    group, k-block, step), one head a step: the dk/dv kernel's walk, a
    k-row's ``nq`` steps over the q-blocks (with a window: over its
    band, from its first q-block; ``last_q``: the sequence's last one;
    block-masked, ``bd``: over the q-blocks that see it, _bd_q_step).
    Every live block adds to all three gradients, so what a row's
    scratch cannot gather stays RESIDENT in VMEM: dq [tq, dh] for the
    query head (every k-row adds to the q-blocks it sees), and, where a
    group shares a key/value head, dk and dv [tk, .] for the group
    (under one head a step they gather in a k-row's scratch, as in
    _dkv_kernel). Each is zeroed at the first step of what it gathers
    and written, once, at the last. ``grads``: the output refs of dq,
    dk and dv, then their three float32 accumulators. Where q and k come
    in two parts (``pe_refs``: blocks of QPe and KPe, _call_parts; one
    key head a query head, ``bhtd_parts``) a step puts its blocks
    together in VMEM, [q | q_pe] and [k | k_pe], and is the wide call's
    step from there: the same five products at the same shapes, so the
    same bits. The accumulators of dq and dk are as wide as both parts,
    and at the end their lanes go to two results each: ``grads`` then
    holds the refs of dq_pe and dk_pe (a QUERY head's own block of it)
    behind dv's. (Measured against the parts as products of their own, 8
    a block, with accumulators of their own: 3.96 ms a call for 4.03 at
    [1, 32, 4096, 128 | 64] alone on a v5e, PR 70.)"""
    # (no dropout, bhtd_bwd_form: the operand is read for ``live`` alone)
    m, kk, r = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    if bd is not None:
        j, bd_live, bd_edge = _bd_q_step(kk, r, bq, bd)
    else:
        j = r if window is None else (kk * bk) // bq + r

    def span(resident, shared):
        """(first, last) step of what an accumulator gathers: a k-row's
        steps; a head's k-rows where it is resident; the group's heads
        where they share it."""
        first, last = r == 0, r == nq - 1
        if resident:
            first = jnp.logical_and(first, kk == 0)
            last = jnp.logical_and(last, kk == nk - 1)
        if resident and shared:
            first = jnp.logical_and(first, m == 0)
            last = jnp.logical_and(last, m == group - 1)
        return first, last

    *outs, dq_acc, dk_acc, dv_acc = grads
    # the results an accumulator is written to, each with its lanes of it
    dq_out, dk_out, dv_out = (((out, slice(None)),) for out in outs[:3])
    if pe_refs is not None:
        own, pe = slice(0, q_ref.shape[3]), slice(q_ref.shape[3], None)
        dq_out = ((outs[0], own), (outs[3], pe))
        dk_out = ((outs[1], own), (outs[4], pe))
    # (accumulator, its outputs, rows of a block, the block a step adds
    # to, its first and last step)
    accs = ((dq_acc, dq_out, bq, j, span(True, False)),
            (dk_acc, dk_out, bk, kk, span(group > 1, True)),
            (dv_acc, dv_out, bk, kk, span(group > 1, True)))

    for acc, _, rows, _, (first, _) in accs:
        def _zero(at, acc=acc, rows=rows):
            acc[at, :] = jnp.zeros((rows, acc.shape[1]), acc.dtype)
        pl.when(first)(functools.partial(_each_block, acc, rows, _zero))

    def _compute(masked=False, at=None):
        # (the whole block, or slab ``at`` of an edge block: its rows of
        # q, do, lse and delta, of k and v, added into the matching rows
        # of the accumulators)
        qs, ks, start = _slab(at, bq, bk)
        mask = bias = None
        if masked and bd is not None:
            mask = lambda s_t: _bd_mask(s_t, j, kk, bq, bd, transposed=True)
        elif masked:
            mask = lambda s_t: _causal_mask(
                s_t[None], j, kk, bq, bk, transposed=True, window=window,
                at=start)[0]
        if bias_ref is not None and bias_ref.dtype == jnp.int32:
            # (a selection's words hold the block's rows 32 a word)
            bias = _unpacked(bias_ref[0, 0, :, ks], start[0],
                             qs.stop - qs.start)
        elif bias_ref is not None:
            per_row = bias_ref.shape[2] > 1
            bias = bias_ref[0, 0, qs if per_row else slice(None), ks]
        q, k = q_ref[0, 0, qs, :], k_ref[0, 0, ks, :]
        if pe_refs is not None:
            q = jnp.concatenate([q, pe_refs[0][0, 0, qs, :]], axis=-1)
            k = jnp.concatenate([k, pe_refs[1][0, 0, ks, :]], axis=-1)
        parts = _bwd_block(
            q, k, v_ref[0, 0, ks, :],
            do_ref[0, 0, qs, :], lse_ref[0, 0, :, qs], delta_ref[0, 0, :, qs],
            bias, scale, mask)
        for (acc, _, rows, idx, _), part, inside in zip(
                accs, parts, (qs, ks, ks)):
            acc[_block_rows(acc, idx, rows, inside), :] += part

    if bd is not None:
        _when_live(_compute, bd_live, j, kk, bq, bk, None, edge=bd_edge)
    elif causal:
        # (a band's steps start at the k-row's first live q-block)
        is_live = (_causal_live(j, kk, bq, bk) if window is None else
                   j <= jnp.minimum(_last_q(kk, bq, bk, window), last_q))
        if live is not None:
            is_live = jnp.logical_and(
                is_live, _chosen_live(seed_ref, live, j, kk, j))
        _when_live(_compute, is_live, j, kk, bq, bk, window, slabs)
    else:
        _compute()

    for acc, acc_outs, rows, _, (_, last) in accs:
        def _write(at, acc=acc, acc_outs=acc_outs):
            for out_ref, lanes in acc_outs:
                out_ref[0, 0, at, :] = acc[at, lanes].astype(out_ref.dtype)
        pl.when(last)(functools.partial(_each_block, acc, rows, _write))


def _step_blocks(causal, k_inner, bq, bk, nq, group=1, window=None,
                 steps=None, bd=None, live=None):
    """-> f(*grid ids) = (i, g, j, kk): batch row, head group, q-block
    and k-block a grid step READS. The grid is (i, g, j, kk) with the k
    axis inner (forward, dq) or (i, g, kk, j) with the q axis inner
    (dk/dv); under ``causal`` the inner index of a dead step is its
    row's nearest live one, so the step fetches nothing. ``group`` > 1
    (one head a step): g is the QUERY head (_row_specs reads K and V at
    g // group); the dk/dv grid is then (i, kv head, kk, r) with r over
    the group's heads and, inside one, its q-blocks. With a ``window``
    the inner axis has ``steps`` steps (a head), the band's width in
    blocks: step r reads the r-th block of its row's band, a dead step
    the band's last. Block-masked (``bd``): step r reads the r-th block
    of its row's own walk (_bd_k_fetch, _bd_q_fetch). Under a selection
    (``live``: its table's (nq, nk), ``_selection``; the scalar-prefetch
    operand is then the last of ``ids``) a step reads the block of the
    inner axis that the table names: its own where it holds a selected
    pair, else a live neighbour's."""
    steps = steps or nq

    def f(*ids):
        i, g = ids[0], ids[1]
        j, kk = (ids[2], ids[3]) if k_inner else (ids[3], ids[2])
        if group > 1 and not k_inner:
            g, j = g * group + j // steps, j % steps
        own = (j, kk)
        if bd is not None and k_inner:
            kk = _bd_k_fetch(j, kk, bq, bd)
        elif bd is not None:
            j = _bd_q_fetch(kk, j, bq, bd)
        elif window is not None and k_inner:
            kk = jnp.minimum(_first_k(j, bq, bk, window) + kk,
                             ((j + 1) * bq - 1) // bk)
        elif window is not None:
            j = jnp.minimum((kk * bk) // bq + j, jnp.minimum(
                _last_q(kk, bq, bk, window), nq - 1))
        elif causal and k_inner:
            kk = _live_k(j, kk, bq, bk)
        elif causal:
            j = jnp.minimum(_live_q(j, kk, bq, bk), nq - 1)
        if live is not None:
            # (-1: nothing of the k-row is selected; the triangle's then)
            named = _table_at(ids[-1], live, i, *own)
            if k_inner:
                kk = jnp.where(named < 0, kk, named)
            else:
                j = jnp.where(named < 0, j, named)
        return i, g, j, kk
    return f


class _Specs(NamedTuple):
    """_row_specs' answer: the specs of q, a [b, h, tq, 1] statistic, the
    same statistic as [b, h, 1, tq] rows, k, out and v; where q and k
    come in two parts, of QPe, KPe and KPe's gradient a query head."""
    q: pl.BlockSpec
    stat: pl.BlockSpec
    row: pl.BlockSpec
    k: pl.BlockSpec
    o: pl.BlockSpec
    v: pl.BlockSpec
    q_pe: Optional[pl.BlockSpec] = None
    k_pe: Optional[pl.BlockSpec] = None
    dk_pe: Optional[pl.BlockSpec] = None


def _row_specs(at, hb, bq, bk, dh, group=1, dv=None, pe=None):
    """Specs read at ``at``'s blocks: a (1, hb, bq, dh) block of q or its
    gradient; a (1, hb, bq, 1) block of a [b, h, tq, 1] statistic;
    a (1, hb, 1, bq) block of the same statistic laid out [b, h, 1, tq];
    a (1, hb, bk, dh) block of k or its gradient (``group`` > 1: a
    (1, 1, bk, dh) block, of the ONE key/value head the step's query
    heads read); and (1, hb, bq, dv) / (1, hb | 1, bk, dv) blocks of out
    and v or their gradients (``dv``: dh where the call has one width).
    ``pe`` = (r, query heads a head of KPe) where q and k come in two
    parts (a key head a query head): a (1, hb, bq, r) block of QPe or
    its gradient at q's index, a (1, 1, bk, r) block of KPe at the ONE
    head the step's query heads read (nothing is copied), and the same
    block of KPe's gradient at k's index, the QUERY head's (the
    backward's: one head a step)."""
    dv = dv or dh
    def q_idx(*ids):
        i, g, j, _ = at(*ids)
        return i, g, j, 0

    def row_idx(*ids):
        i, g, j, _ = at(*ids)
        return i, g, 0, j

    def read_by(heads, width):
        # the spec of an operand [b, h / heads, tk, width] that ``heads``
        # query heads read one head of: a block of the step's hb heads
        # where each has its own (heads 1), else ONE head's block, which
        # the step's heads share (hb divides ``heads``: _pick_tile gives
        # a group one head a step, bhtd_fwd_tile an even one two)
        def idx(*ids):
            i, g, _, kk = at(*ids)
            if heads > 1:
                g = (g if hb == 1 else g * hb) // heads
            return i, g, kk, 0
        return pl.BlockSpec((1, hb if heads == 1 else 1, bk, width), idx)

    specs = _Specs(q=pl.BlockSpec((1, hb, bq, dh), q_idx),
                   stat=pl.BlockSpec((1, hb, bq, 1), q_idx),
                   row=pl.BlockSpec((1, hb, 1, bq), row_idx),
                   k=read_by(group, dh),
                   o=pl.BlockSpec((1, hb, bq, dv), q_idx),
                   v=read_by(group, dv))
    if pe is None:
        return specs
    r, pe_group = pe
    return specs._replace(
        q_pe=pl.BlockSpec((1, hb, bq, r), q_idx),
        k_pe=read_by(pe_group, r),
        # (the backward's: a query head's own block, one head a step)
        dk_pe=read_by(1, r))


def _bias_spec(bias, at, hb, bq, bk):
    """BlockSpec for the stored-rank bias [b, 1|h, 1|tq, tk], read at
    ``at``'s blocks; of a selection [b, 1, tq / 32, tk] int32
    (``_selection``), the (bq / 32, bk) words of a block's pairs."""
    per_head, per_row = bias.shape[1] > 1, bias.shape[2] > 1
    if bias.dtype == jnp.int32:
        bq //= 32

    def idx(*ids):
        i, g, j, kk = at(*ids)
        return i, g if per_head else 0, j if per_row else 0, kk

    return pl.BlockSpec(
        (1, hb if per_head else 1, bq if per_row else 1, bk), idx)


def _reference_scores(q, k, bias, scale, causal, window=None,
                      block_diffusion=None, selected=None):
    """Scaled scores + bias + causal (and window) mask, or block
    diffusion's (``bd_visible``) — the ONE copy
    both the dense forward and its lse statistic derive from (the
    ring-attention merge combines (out, lse), so they must never
    desynchronize)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias.astype(s.dtype)
    if _halves(block_diffusion, causal, window, q.shape[2], k.shape[2]):
        s = jnp.where(bd_visible(q.shape[2], int(block_diffusion))[None, None],
                      s, _NEG_INF)
    if causal:
        tq, tk = q.shape[2], k.shape[2]
        ago = jnp.arange(tq)[:, None] - jnp.arange(tk)[None, :]
        mask = ago >= 0
        if window is not None:
            mask = jnp.logical_and(mask, ago < window)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    if selected is not None:    # [b, tq, tk]: every head's mask
        s = jnp.where(selected[:, None] != 0, s, _NEG_INF)
    return s


def _reference_attention_with_lse(q, k, v, bias, scale, p_drop=0.0,
                                  seed=None, causal=False, window=None,
                                  block_diffusion=None, selected=None):
    """(out, lse) from ONE score tensor — the fallback twin of the
    kernels' contract. out and lse must never derive from separately
    constructed scores (different dtype promotion would desynchronize
    them at exactly the tolerance the ring merge relies on). K and V
    with fewer heads than Q (grouped-query attention) are repeated
    here: the composition, unlike the kernels, makes the copies."""
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = _reference_scores(q, k, bias, scale, causal, window, block_diffusion,
                          selected)
    lse = jax.scipy.special.logsumexp(s, axis=-1, keepdims=True)
    p = jax.nn.softmax(s, axis=-1)
    if p_drop > 0.0:
        key = jax.random.PRNGKey(0 if seed is None else jnp.asarray(seed))
        keep = jax.random.bernoulli(key, 1.0 - p_drop, p.shape)
        p = jnp.where(keep, p / (1.0 - p_drop), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v), lse


def _reference_attention(q, k, v, bias, scale, p_drop=0.0, seed=None,
                         causal=False, window=None, block_diffusion=None,
                         selected=None):
    return _reference_attention_with_lse(q, k, v, bias, scale, p_drop,
                                         seed, causal, window,
                                         block_diffusion, selected)[0]


def _seed_arr(seed):
    """The kernels' scalar-prefetch operand, (2,) int32: [dropout seed,
    global index of this call's first batch row]. ``seed`` is a scalar
    (row offset 0) or already that pair — a batch-sharded caller
    (ops/attention_ops.py) passes its shard's offset so the masks do not
    depend on how many devices split the batch."""
    if seed is None:
        return jnp.zeros((2,), jnp.int32)
    seed = jnp.asarray(seed, jnp.int32).reshape((-1,))
    if seed.shape[0] == 1:
        seed = jnp.concatenate([seed, jnp.zeros((1,), jnp.int32)])
    return seed


# Every pl.pallas_call below carries name="<family>.<what>.<pass>" as
# perf/ reads it: the family is ``attn``, <what> the kernel family of
# attention_ops' dispatch counter (bhtd, bthd_small, bthd_kblock), the
# passes fwd, bwd, bwd_dq, bwd_dkv: jax puts a kernel's name on the HLO
# instruction AND into its op_name, under the sdpa op's scope (core/interp.exec_ops), so
# a device trace tells one attention kernel from another and forward
# from backward.


def _result(operands, shape, dtype):
    """out_shape entry of a pallas_call over ``operands``: the result
    varies over the same manual mesh axes as they do. Inside a shard_map
    that checks varying axes the annotation is required; outside one the
    set is empty."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _seed_cotangent(seed):
    """Symbolic-zero cotangent for the integer seed operand."""
    if seed is None:
        return None
    import numpy as _np

    return _np.zeros(_np.shape(seed), jax.dtypes.float0)


# ---------------------------------------------------------------------------
# functional entry points (used directly by the sdpa op pair)
# ---------------------------------------------------------------------------


def _call_parts(kernel, at, tile, q, k, v, bias, pe=None):
    """What the three calls share: -> (the kernel, the specs and the
    operands of q, k, v and the bias if there is one, _row_specs). With
    no bias the kernel's bias_ref slot (the fifth) is None. ``pe`` =
    (QPe, KPe): two more operands behind v, which the kernel gets as
    ``pe_refs`` (such a call has no bias: ``bhtd_parts``)."""
    rows = _row_specs(at, *tile, q.shape[3], q.shape[1] // k.shape[1],
                      v.shape[3],
                      pe and (pe[0].shape[3], q.shape[1] // pe[1].shape[1]))
    specs, args = [rows.q, rows.k, rows.v], [q, k, v]
    if pe is not None:
        body = kernel
        kernel = lambda *refs, **kw: body(*refs[:4], None, *refs[6:],
                                          pe_refs=refs[4:6], **kw)
        specs += [rows.q_pe, rows.k_pe]
        args += pe
    elif bias is None:
        body = kernel
        kernel = lambda *refs, **kw: body(*refs[:4], None, *refs[4:], **kw)
    else:
        specs.append(_bias_spec(bias, at, *tile))
        args.append(bias)
    return kernel, specs, args, rows


def _kv_group(q, k, p_drop):
    """Query heads a key/value head of the call: 1, or under
    grouped-query attention h / kv heads (K and V [b, kv heads, tk,
    dh])."""
    h, hk = q.shape[1], k.shape[1]
    if h % hk:
        raise ValueError(f"attention: {h} query heads do not divide over "
                         f"{hk} key/value heads")
    if h != hk and p_drop > 0.0:
        raise ValueError(
            "attention dropout with grouped key/value heads: the dk/dv "
            "kernel's mask stream is keyed by its grid's head group")
    return h // hk


def _band(window, causal, tq, tk):
    """The ``window`` a call runs with: None where it forgets nothing
    (no window, or one as long as the keys: the causal call itself)."""
    if window is None:
        return None
    if not causal or tq != tk or window < 1:
        raise ValueError(
            f"attention: window={window} needs causal self-attention "
            f"(causal={causal}, tq={tq}, tk={tk})")
    return None if window >= tk else int(window)


def _k_steps(window, nq, nk, bq, bk):
    """The forward and dq grids' inner axis: a q-row's band in blocks."""
    if window is None:
        return nk
    return _band_steps(nq, lambda j: max(j * bq - window + 1, 0) // bk,
                       lambda j: ((j + 1) * bq - 1) // bk)


def _q_steps(window, nq, nk, bq, bk):
    """The dk/dv grid's inner axis (a head): a k-row's band in blocks."""
    if window is None:
        return nq
    return _band_steps(nk, lambda kk: (kk * bk) // bq, lambda kk: min(
        ((kk + 1) * bk + window - 2) // bq, nq - 1))


def _two_parts(q, k, v, q_pe, k_pe, q_block, k_block, *not_plain):
    """(q_pe, k_pe) of a call whose queries and keys come in two parts,
    None for a call in one; an error where the kernels do not take the
    parts (``bhtd_parts``: the caller assembles q and k then).
    ``not_plain``: the call's bias, dropout rate, window and block mask,
    as the entry points hold them."""
    if q_pe is None and k_pe is None:
        return None
    if q_pe is None or k_pe is None:
        raise ValueError("attention: q_pe and k_pe come together")
    h, r, hp = q.shape[1], q_pe.shape[3], k_pe.shape[1]
    if not (q_pe.shape == q.shape[:3] + (r,) and q_pe.dtype == q.dtype
            and k_pe.shape == (k.shape[0], hp, k.shape[2], r)
            and k_pe.dtype == k.dtype and bhtd_parts(
                h, q.shape[2], k.shape[2], q_block, k_block, dh=q.shape[3],
                r=r, hp=hp, group=h // k.shape[1], dv=v.shape[3],
                itemsize=q.dtype.itemsize, plain=not any(not_plain))):
        raise ValueError(
            f"attention: the kernels do not take q_pe {q_pe.shape} "
            f"{q_pe.dtype} and k_pe {k_pe.shape} {k_pe.dtype} beside q "
            f"{q.shape} and k {k.shape} as operands of their own "
            f"(flash_attention.bhtd_parts)")
    return q_pe, k_pe


def bhtd_selected(h, tq, tk, q_block=None, k_block=None, *, dh, group=1,
                  dv=None, itemsize=2, plain=True, blocks=None):
    """Do ``attn.bhtd.fwd`` and the ONE ``attn.bhtd.bwd`` take this call's
    SELECTION (``flash_attention_fwd``'s ``selected``: which keys each
    query reads, a device value) as an operand? Where the call is
    ``plain`` beside ``causal`` (the caller's word: self-attention, no
    bias, dropout, window, block mask or second part), has a tile whose
    backward is the ONE call (the split pair carries no selection) and
    that tile's blocks are the selection's own, ``blocks`` = its live
    table's (nq, nk): a q-block's bits are packed together
    (``dsa_score.pack_rows``). The one place that decides: the entry
    points and the sdpa op, which runs the dense composition under a
    [t, t] mask where this says no (the dispatch counter's ``sel``
    label)."""
    if not (plain and tq == tk and bhtd_bwd_form(
            h, tq, tk, q_block, k_block, dh=dh, group=group, dv=dv,
            itemsize=itemsize) == "fused"):
        return False
    _, bq, bk = bhtd_tile(h, tq, tk, q_block, k_block, dh=dh, group=group,
                          dv=dv, itemsize=itemsize)
    return tuple(blocks) == (tq // bq, tk // bk) and bq % 32 == 0


def _only_causal(causal, bias, p_drop, window, bd, pe):
    """``bhtd_selected``'s ``plain`` as the entry points hold it: causal,
    and neither a bias, dropout, a window, a block mask nor a second
    part."""
    return bool(causal and bias is None
                and not (p_drop or window or bd or pe))


def _fetched(live, axis):
    """``live`` [b, nq, nk] (nonzero: some pair of block (j, kk) is
    selected) -> per block the index along ``axis`` (the call's inner
    one: 2, the k-blocks of a q-row; 1, the q-blocks of a k-row) of the
    block its step fetches: its own where it is live, else the next live
    one of the walk, else the last one before, else -1 (a k-row nobody
    reads). Along a walk the answer never falls, so a row's live blocks
    are each copied once and nothing else is."""
    n = live.shape[axis]
    at = jnp.arange(n, dtype=jnp.int32).reshape(
        [-1 if a == axis else 1 for a in range(3)])
    ahead = jax.lax.cummin(jnp.where(live != 0, at, n), axis=axis,
                           reverse=True)
    behind = jax.lax.cummax(jnp.where(live != 0, at, -1), axis=axis)
    return jnp.where(ahead < n, ahead, behind)


def _selected_mask(selected, live):
    """A selection as the dense composition's [b, tq, tk] mask (None:
    the call has none)."""
    if selected is None:
        return None
    return dsa_score.unpack(selected, selected.shape[2] // live.shape[1])


def _selection(selected, live, q, k, seed_arr, axis):
    """-> (the selection as the kernels' bias-slot operand
    [b, 1, tq / 32, tk] int32, the scalar-prefetch operand with the
    table of the blocks to fetch behind the seed pair (``_fetched``
    along ``axis``), the table's (nq, nk)) of a call ``bhtd_selected``
    takes: ``selected`` [b, tq / 32, tk] int32, ``live`` [b, nq, nk]
    int32, its blocks the tile's."""
    b, _, tq, _ = q.shape
    if selected.shape != (b, tq // 32, k.shape[2]) or (
            selected.dtype != jnp.int32):
        raise ValueError(
            f"attention: a selection is [b, tq / 32, tk] int32 beside "
            f"causal (got {selected.shape} {selected.dtype} for q "
            f"{q.shape})")
    table = _fetched(live.astype(jnp.int32), axis)
    return (selected[:, None],
            jnp.concatenate([seed_arr, table.reshape(-1)]), live.shape[1:])


def flash_attention_fwd(q, k, v, bias=None, seed=None, scale=None,
                        p_drop: float = 0.0,
                        q_block: Optional[int] = None,
                        k_block: Optional[int] = None,
                        causal: bool = False,
                        window: Optional[int] = None,
                        block_diffusion: Optional[int] = None,
                        q_pe=None, k_pe=None, selected=None, live=None):
    """-> (out, lse) with lse [b, h, tq, 1] f32 — REAL logsumexp rows on
    every path including the dense fallback (the ring-attention merge
    consumes them; the fallback backward still recomputes via vjp).

    The kernel writes lse as ``bhtd_stats_form`` says. "rows": a
    [b, h, 1, tq] result, reshaped here to the contract's [b, h, tq, 1];
    ``flash_attention_bwd`` reshapes it back and inside one jit the two
    fold to nothing, so the backward reads what the forward wrote. As a
    [.., tq, 1] float32 result of the kernel each row's value owns a
    (8, 128) tile's lane row on the chip, 512 bytes for 4: the "column"
    form, written only where a q block is no whole number of lane tiles
    of a row.

    ``causal=True`` applies the future mask IN-KERNEL (block-position
    iota compare) and skips fully-masked k-blocks outright — no [tq, tk]
    bias tensor exists anywhere, preserving the O(t) HBM property for
    decoder self-attention, and the dead upper-triangle blocks cost
    neither MXU time nor a fetch (the causal ~2x). ``window``: each
    query sees the last ``window`` positions only; the grid walks the
    band and no block outside it is a step at all. ``block_diffusion``
    (with neither): the row is a noised and a clean copy in blocks of
    that many positions, under block diffusion's mask (module
    docstring). ``q_pe`` [b, h, tq, r], ``k_pe`` [b, hp, tk, r]: the
    second part of the queries and keys where they come in two, the
    scores scale * (q k^T + q_pe k_pe^T) with query head i reading
    k_pe's head i // (h / hp), and the default scale 1 / sqrt(dh + r);
    only a call ``bhtd_parts`` takes.

    ``selected`` [b, tq / 32, tk] int32 with ``live`` [b, nq, nk] int32
    (beside ``causal``; ``dsa_select``'s pair): query p reads key s only
    where its bit is set (``dsa_score.pack_rows``, a q-block of tq / nq
    queries), every head alike: a mask that is DATA (a learned sparse
    attention's top-k), read in blocks of words beside K and V through
    the bias operand's slot; the walk is the causal triangle's. ``live``
    is 0 where a block holds no selected pair: by scalar prefetch such
    a block's step computes nothing and fetches nothing (``_fetched``).
    Only a call ``bhtd_selected`` takes; elsewhere the dense composition
    under the mask."""
    if p_drop > 0.0 and seed is None:
        raise ValueError(
            "flash_attention: p_drop > 0 requires a per-step `seed`; "
            "without one the SAME mask would be applied every step, which "
            "is not dropout"
        )
    b, h, tq, dh = q.shape
    tk, dv = k.shape[2], v.shape[3]
    group = _kv_group(q, k, p_drop)
    bd = _halves(block_diffusion, causal, window, tq, tk)
    window = _band(window, causal, tq, tk)
    pe = _two_parts(q, k, v, q_pe, k_pe, q_block, k_block, bias is not None,
                    p_drop, window, bd)
    if pe is not None:      # (the tile and the scale of the WHOLE head)
        dh += q_pe.shape[3]
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    chosen = selected is not None and bhtd_selected(
        h, tq, tk, q_block, k_block, dh=dh, group=group, dv=dv,
        itemsize=q.dtype.itemsize, blocks=live.shape[1:],
        plain=_only_causal(causal, bias, p_drop, window, bd, pe))
    # (the forward's own heads a step at the call's blocks; the rows of
    # lse go by the blocks, which are the backward's tile's too)
    held = dict(selected=chosen, bias=None if bias is None else bias.shape)
    tile = bhtd_fwd_tile(
        h, tq, tk, q_block, k_block, dh=dh, group=group, dv=dv,
        block_diffusion=block_diffusion, itemsize=q.dtype.itemsize,
        p_drop=p_drop, pe_group=pe and h // k_pe.shape[1], **held)
    if tile is None or (selected is not None and not chosen):
        # REAL logsumexp rows, not placeholder zeros: the ring-attention
        # merge combines per-block (o, lse) partials, and both must
        # derive from one score tensor (_reference_attention_with_lse).
        return _reference_attention_with_lse(
            q, k, v, bias, scale, p_drop,
            seed if p_drop > 0.0 else None, causal=causal, window=window,
            block_diffusion=block_diffusion,
            selected=_selected_mask(selected, live))

    hb, bq, bk = tile
    seed_arr, live_at = _seed_arr(seed), None
    if chosen:
        bias, seed_arr, live_at = _selection(selected, live, q, k, seed_arr,
                                             2)
    ng, nq = h // hb, tq // bq
    # the inner axis: the key blocks, or those of a row's band, or a
    # block-masked row's own block and the clean half's
    nk = (bd[1] // bq + 1 if bd else
          _k_steps(window, nq, tk // bk, bq, bk))
    kernel, in_specs, args, rows = _call_parts(
        _fwd_kernel,
        _step_blocks(causal, True, bq, bk, nq, group, window, nk, bd,
                     live_at), tile, q, k, v, bias, pe)
    # (heads that the forward's own tile put into a step are a chain
    # each; heads that bhtd_tile batched, a short row's, one chain)
    batched = tile == bhtd_tile(h, tq, tk, q_block, k_block, dh=dh,
                                group=group, dv=dv,
                                block_diffusion=block_diffusion,
                                itemsize=q.dtype.itemsize)
    kernel = functools.partial(kernel, scale=scale, nk=nk, ng=ng,
                               p_drop=p_drop, causal=causal, window=window,
                               bd=bd, chain=None if batched else 1)
    if live_at is not None:
        kernel = functools.partial(kernel, live=live_at)
    operands = (seed_arr, *args)
    lse_spec, lse_shape = rows.stat, (b, h, tq, 1)
    if bhtd_stats_form(tile, tq) == "rows":
        lse_spec, lse_shape = rows.row, (b, h, 1, tq)
    limit = _fwd_vmem_limit(hb, _kv_blocks(hb, group), bq, bk, dh, dv,
                            q.dtype.itemsize, bhtd_stats_form(tile, tq),
                            **held)
    asked = {} if limit is None else dict(
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=limit))
    out, lse = pl.pallas_call(
        kernel, name="attn.bhtd.fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, ng, nq, nk),
            in_specs=in_specs,
            out_specs=[rows.o, lse_spec],
            scratch_shapes=[
                pltpu.VMEM((hb, bq, 128), jnp.float32),
                pltpu.VMEM((hb, bq, 128), jnp.float32),
                pltpu.VMEM((hb, bq, dv), jnp.float32),
            ],
        ),
        out_shape=[
            _result(operands, (b, h, tq, dv), q.dtype),
            _result(operands, lse_shape, jnp.float32),
        ],
        interpret=_INTERPRET, **asked,
    )(*operands)
    # (inside one jit this reshape and the backward's, back into rows,
    # fold to nothing; a consumer of the column gets it from XLA)
    return out, lse.reshape(b, h, tq, 1)


def _fused_bwd(q, k, v, bias, seed_arr, g, lse, delta, tile, scale, causal,
               window, bd=None, pe=None, live=None):
    """dq, dk, dv as ONE call (_bwd_kernel); ``delta`` with the lse
    cotangent folded in, ``window`` as _band gives it, ``bd`` as
    _halves. ``pe`` = (q_pe, k_pe): dq_pe and dk_pe [b, h, tk, r], a
    QUERY head's each, behind them."""
    b, h, tq, dh = q.shape
    tk, dv = k.shape[2], v.shape[3]
    group = h // k.shape[1]
    _, bq, bk = tile
    nq, nk = tq // bq, tk // bk
    # (block-masked: the first clean k-row is seen by every q-block)
    q_steps = nq if bd else _q_steps(window, nq, nk, bq, bk)
    block_of = _step_blocks(causal, False, bq, bk, nq, 1, window, q_steps,
                            bd, live)

    def at(i, hk, m, kk, r, *seed_ref):
        # (the grid's heads: key/value head, then the member of its group)
        return block_of(i, hk * group + m, kk, r, *seed_ref)

    kernel, specs, args, rows = _call_parts(_bwd_kernel, at, tile, q, k, v,
                                            bias, pe)
    sub = bhtd_edge_tile(tile, causal)
    kernel = functools.partial(
        kernel, scale=scale, nq=q_steps, nk=nk, group=group, causal=causal,
        window=window, last_q=nq - 1,
        slabs=sub and _edge_slabs(tq, tk, bq, bk, sub, window), bd=bd)
    if live is not None:    # (the live table's (nq, nk): _selection)
        kernel = functools.partial(kernel, live=live)
    # a resident gradient: all rows of one head, one block of the output
    dq_spec = pl.BlockSpec((1, 1, tq, dh),
                           lambda i, hk, m, *_: (i, hk * group + m, 0, 0))
    dk_spec, dv_spec, kv_rows = rows.k, rows.v, bk
    if group > 1:
        dk_spec, dv_spec = (pl.BlockSpec((1, 1, tk, d),
                                         lambda i, hk, *_: (i, hk, 0, 0))
                            for d in (dh, dv))
        kv_rows = tk
    # lse and delta as [b, h, 1, tq] rows, as the dk/dv kernel takes them
    operands = (seed_arr, *args, g, lse.reshape(b, h, 1, tq),
                delta.reshape(b, h, 1, tq))
    # (a gradient's output block and its result)
    grads = [(dq_spec, q), (dk_spec, k), (dv_spec, v)]
    r = 0
    if pe is not None:      # (group 1: bhtd_parts)
        r = pe[0].shape[3]
        grads += [
            (pl.BlockSpec((1, 1, tq, r), dq_spec.index_map), pe[0]),
            (rows.dk_pe, jax.ShapeDtypeStruct((b, h, tk, r), pe[1].dtype))]
    return pl.pallas_call(
        kernel, name="attn.bhtd.bwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h // group, group, nk, q_steps),
            in_specs=specs + [rows.o, rows.row, rows.row],
            out_specs=[spec for spec, _ in grads],
            scratch_shapes=[
                pltpu.VMEM((tq, dh + r), jnp.float32),
                pltpu.VMEM((kv_rows, dh + r), jnp.float32),
                pltpu.VMEM((kv_rows, dv), jnp.float32)],
        ),
        out_shape=[_result(operands, x.shape, x.dtype) for _, x in grads],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_bwd_vmem_limit(
                tq, tk, dh + r, dv, group, bq, bk, q.dtype.itemsize)),
        interpret=_INTERPRET,
    )(*operands)


def flash_attention_bwd(q, k, v, bias, seed, out, lse, g, scale=None,
                        p_drop: float = 0.0,
                        q_block: Optional[int] = None,
                        k_block: Optional[int] = None,
                        causal: bool = False, g_lse=None,
                        window: Optional[int] = None,
                        block_diffusion: Optional[int] = None,
                        q_pe=None, k_pe=None, selected=None, live=None):
    """-> (dq, dk, dv), consuming the forward's saved (out, lse); of a
    call in two parts (``q_pe``, ``k_pe``: ``flash_attention_fwd``) ->
    (dq, dk, dv, dq_pe, dk_pe). The kernel writes dk_pe a QUERY head, in
    the call's dtype, and XLA sums the heads that share a head of k_pe
    behind it: the rounding and the sum of a shared head copied h / hp
    times.

    ``g_lse``: optional cotangent of the lse OUTPUT ([b, h, tq, 1]).
    The lse rows are a real differentiated quantity for consumers like
    the ring-attention merge (block weights exp(lse_blk - lse_comb)).
    dlse/ds = p, so the lse cotangent phi folds EXACTLY into the
    existing backward as ds = p*(dp - (delta - phi)) — one subtraction
    on the per-row delta, no kernel changes. ``selected``, ``live``: the
    forward's (``flash_attention_fwd``); no gradient reaches them."""
    b, h, tq, dh = q.shape
    tk, dv = k.shape[2], v.shape[3]
    group = _kv_group(q, k, p_drop)
    bd = _halves(block_diffusion, causal, window, tq, tk)
    window = _band(window, causal, tq, tk)
    pe = _two_parts(q, k, v, q_pe, k_pe, q_block, k_block, bias is not None,
                    p_drop, window, bd)
    if pe is not None:      # (the tile and the scale of the WHOLE head)
        dh += q_pe.shape[3]
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    tile = bhtd_tile(h, tq, tk, q_block, k_block, dh=dh, group=group, dv=dv,
                     block_diffusion=block_diffusion,
                     itemsize=q.dtype.itemsize)
    chosen = selected is not None and bhtd_selected(
        h, tq, tk, q_block, k_block, dh=dh, group=group, dv=dv,
        itemsize=q.dtype.itemsize, blocks=live.shape[1:],
        plain=_only_causal(causal, bias, p_drop, window, bd, pe))
    if tile is None or (selected is not None and not chosen):
        mask = _selected_mask(selected, live)

        def f(q, k, v):
            return _reference_attention_with_lse(
                q, k, v, bias, scale, p_drop,
                seed if p_drop > 0.0 else None, causal=causal,
                window=window, block_diffusion=block_diffusion,
                selected=mask)

        _, vjp = jax.vjp(f, q, k, v)
        return vjp((g, jnp.zeros((b, h, tq, 1), jnp.float32)
                    if g_lse is None else g_lse))

    hb, bq, bk = tile
    ng, nq, nk = h // hb, tq // bq, tk // bk
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [b, h, tq, 1]
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    seed_arr = _seed_arr(seed)
    if chosen:              # (bhtd_selected: the ONE call)
        sel, seed_arr, live_at = _selection(selected, live, q, k, seed_arr,
                                            1)
        return _fused_bwd(q, k, v, sel, seed_arr, g, lse, delta, tile,
                          scale, causal, None, live=live_at)
    if pe is not None:      # (bhtd_parts: the ONE call)
        *grads, dk_pe = _fused_bwd(q, k, v, None, seed_arr, g, lse, delta,
                                   tile, scale, causal, None, pe=pe)
        hp = k_pe.shape[1]
        return (*grads, jnp.sum(
            dk_pe.reshape(b, hp, h // hp, tk, -1), axis=2))
    # (a block-masked call has a tile only where it is fused and has no
    # dropout: bhtd_tile)
    if bd is not None or _fused_fits(tile, tq, tk, dh, dv, group,
                                     q.dtype.itemsize, p_drop):
        return _fused_bwd(q, k, v, bias, seed_arr, g, lse, delta, tile,
                          scale, causal, window, bd)
    kw = dict(scale=scale, ng=ng, p_drop=p_drop, causal=causal,
              window=window)
    # the inner axes: all nk key blocks a q-row and all nq query blocks
    # a k-row, or, with a window, those of the row's band
    k_steps = _k_steps(window, nq, nk, bq, bk)
    q_steps = _q_steps(window, nq, nk, bq, bk)

    # --- dq: grid (b, ng, nq, nk), k-blocks inner ---
    kernel, specs, args, rows = _call_parts(
        _dq_kernel,
        _step_blocks(causal, True, bq, bk, nq, group, window, k_steps), tile,
        q, k, v, bias)
    kernel = functools.partial(kernel, nk=k_steps, **kw)
    operands = (seed_arr, *args, g, lse, delta)
    dq = pl.pallas_call(
        kernel, name="attn.bhtd.bwd_dq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, ng, nq, k_steps),
            in_specs=specs + [rows.o, rows.stat, rows.stat],
            out_specs=rows.q,
            scratch_shapes=[pltpu.VMEM((hb, bq, dh), jnp.float32)],
        ),
        out_shape=_result(operands, (b, h, tq, dh), q.dtype),
        interpret=_INTERPRET,
    )(*operands)

    # --- dk/dv: grid (b, ng, nk, nq), q-blocks inner ---
    # lse and delta as [b, h, 1, tq] rows: the kernel works on the
    # transposed score block and wants them along the lanes. As
    # [b, h, tq, 1] columns XLA pads each to 128 lanes for the call (64 MB
    # at OLMoE's shape), the q axis being this grid's inner one a step
    # fetches bq x 512 B of each, and the kernel transposes both. Only a
    # q block off the 128-lane tiling (a caller's q_block of 64) keeps
    # that form: it cannot be cut from a row.
    kernel, specs, args, rows = _call_parts(
        _dkv_kernel,
        _step_blocks(causal, False, bq, bk, nq, group, window, q_steps), tile,
        q, k, v, bias)
    kernel = functools.partial(kernel, nq=q_steps, last_q=nq - 1, **kw)
    dkv_grid = (b, ng, nk, q_steps)
    if group > 1:
        # a step's dk, dv block is one key/value head's: the inner axis
        # walks the group's query heads, and the scratch sums them
        kernel = functools.partial(kernel, group=group)
        dkv_grid = (b, h // group, nk, group * q_steps)
    stats, stat_spec = [lse, delta], rows.stat
    if bhtd_stats_form(tile, tq) == "rows":
        stat_spec = rows.row
        stats = [x.reshape(b, h, 1, tq) for x in stats]
    operands = (seed_arr, *args, g, *stats)
    dk, dv = pl.pallas_call(
        kernel, name="attn.bhtd.bwd_dkv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=dkv_grid,
            in_specs=specs + [rows.o, stat_spec, stat_spec],
            out_specs=[rows.k, rows.v],
            scratch_shapes=[
                pltpu.VMEM((hb, bk, dh), jnp.float32),
                pltpu.VMEM((hb, bk, dv), jnp.float32),
            ],
        ),
        out_shape=[
            _result(operands, k.shape, k.dtype),
            _result(operands, v.shape, v.dtype),
        ],
        interpret=_INTERPRET,
    )(*operands)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# standalone custom-vjp wrapper (public API; the Program IR path uses the
# sdpa/sdpa_grad op pair instead so the backward reuses saved stats)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def flash_attention(q, k, v, bias=None, seed=None,
                    scale: Optional[float] = None, p_drop: float = 0.0,
                    q_block: Optional[int] = None,
                    k_block: Optional[int] = None,
                    causal: bool = False,
                    window: Optional[int] = None,
                    block_diffusion: Optional[int] = None):
    """o = dropout(softmax(q k^T * scale + bias)) v.

    ``seed``: int32 scalar array driving attention dropout (ignored when
    p_drop == 0). See the module docstring for the bias-gradient caveat.
    """
    out, _ = flash_attention_fwd(q, k, v, bias, seed, scale, p_drop,
                                 q_block, k_block, causal, window,
                                 block_diffusion)
    return out


def _vjp_fwd(q, k, v, bias, seed, scale, p_drop, q_block, k_block,
             causal=False, window=None, block_diffusion=None):
    out, lse = flash_attention_fwd(q, k, v, bias, seed, scale, p_drop,
                                   q_block, k_block, causal, window,
                                   block_diffusion)
    return out, (q, k, v, bias, seed, out, lse)


def _vjp_bwd(scale, p_drop, q_block, k_block, causal, window,
             block_diffusion, res, g, g_lse=None):
    q, k, v, bias, seed, out, lse = res
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if bhtd_family(q.shape[1], q.shape[2], k.shape[2],
                    q_block, k_block, dh=q.shape[3],
                    group=q.shape[1] // k.shape[1],
                    dv=v.shape[3], block_diffusion=block_diffusion,
                    itemsize=q.dtype.itemsize) == "bhtd":
        dq, dk, dv = flash_attention_bwd(q, k, v, bias, seed, out, lse, g,
                                         scale, p_drop, q_block, k_block,
                                         causal, g_lse=g_lse, window=window,
                                         block_diffusion=block_diffusion)
        # Pallas path: bias is mask plumbing, cotangent intentionally zero
        # (see module docstring).
        dbias = None if bias is None else jnp.zeros_like(bias)
    else:
        sd = seed if p_drop > 0.0 else None
        glse = (jnp.zeros_like(lse) if g_lse is None else g_lse)

        def out_and_lse(a, b, c, bb):
            return _reference_attention_with_lse(
                a, b, c, bb, scale, p_drop, sd, causal,
                _band(window, causal, a.shape[2], b.shape[2]),
                block_diffusion)

        if bias is None:
            _, vjp = jax.vjp(
                lambda a, b, c: out_and_lse(a, b, c, None), q, k, v)
            dq, dk, dv = vjp((g, glse))
            dbias = None
        else:
            _, vjp = jax.vjp(out_and_lse, q, k, v, bias)
            dq, dk, dv, dbias = vjp((g, glse))
    return dq, dk, dv, dbias, _seed_cotangent(seed)


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)


# --- custom-vjp wrapper ---
#
# pallas_call has no JVP rule, so any path that differentiates the forward
# through jax.vjp (the scan-over-layers grad, ring-attention fallback,
# ad-hoc jax.grad over a model fn) would fail on TPU. This wrapper teaches
# autodiff to use the blocked backward kernels instead; the paired
# `scaled_dot_product_attention_grad` op remains for the unrolled Program
# path, sharing the same kernels.


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def flash_attention_with_lse(q, k, v, bias=None, seed=None,
                             scale: Optional[float] = None,
                             p_drop: float = 0.0,
                             q_block: Optional[int] = None,
                             k_block: Optional[int] = None,
                             causal: bool = False,
                             window: Optional[int] = None,
                             block_diffusion: Optional[int] = None,
                             q_pe=None, k_pe=None, selected=None, live=None):
    """(out, lse) variant of ``flash_attention`` — same backward rule
    (shared ``_vjp_bwd``: blocked Pallas kernels, true dbias on the dense
    fallback, float0 seed cotangent). The sdpa op uses this so its saved
    Lse output exists AND jax.vjp through the op (scan-over-layers grad)
    works despite pallas_call having no JVP rule. ``q_pe``, ``k_pe``:
    the queries' and keys' second part (``flash_attention_fwd``), with
    cotangents of their own. ``selected``, ``live``: a selection and its
    live table (``flash_attention_fwd``), integers without a cotangent."""
    return flash_attention_fwd(q, k, v, bias, seed, scale, p_drop,
                               q_block, k_block, causal, window,
                               block_diffusion, q_pe, k_pe, selected, live)


def _fa_lse_vjp_fwd(q, k, v, bias, seed, scale, p_drop, q_block, k_block,
                    causal=False, window=None, block_diffusion=None,
                    q_pe=None, k_pe=None, selected=None, live=None):
    out, lse = flash_attention_fwd(q, k, v, bias, seed, scale, p_drop,
                                   q_block, k_block, causal, window,
                                   block_diffusion, q_pe, k_pe, selected,
                                   live)
    return (out, lse), (q, k, v, bias, seed, out, lse, q_pe, k_pe, selected,
                        live)


def _fa_lse_vjp_bwd(scale, p_drop, q_block, k_block, causal, window,
                    block_diffusion, res, gs):
    g, g_lse = gs
    *res, q_pe, k_pe, selected, live = res
    q, k, v, bias, seed, out, lse = res
    if selected is not None:
        dq, dk, dv = flash_attention_bwd(
            q, k, v, bias, seed, out, lse, g.astype(q.dtype), scale, p_drop,
            q_block, k_block, causal, g_lse=g_lse, selected=selected,
            live=live)
        return (dq, dk, dv, None, _seed_cotangent(seed), None, None,
                _seed_cotangent(selected), _seed_cotangent(live))
    if q_pe is None:
        return (*_vjp_bwd(scale, p_drop, q_block, k_block, causal, window,
                          block_diffusion, res, g.astype(q.dtype),
                          g_lse=g_lse), None, None, None, None)
    dq, dk, dv, dq_pe, dk_pe = flash_attention_bwd(
        q, k, v, bias, seed, out, lse, g.astype(q.dtype), scale, p_drop,
        q_block, k_block, causal, g_lse=g_lse, q_pe=q_pe, k_pe=k_pe)
    return dq, dk, dv, None, _seed_cotangent(seed), dq_pe, dk_pe, None, None


flash_attention_with_lse.defvjp(_fa_lse_vjp_fwd, _fa_lse_vjp_bwd)



# ---------------------------------------------------------------------------
# BTHD fast path: q/k/v in [b, t, h, dh] — the layout the attention
# projections naturally produce (reshape of [b, t, d]; no head transpose).
# Profiling the transformer bench showed the BHTD kernels cost ~15 ms/step
# in pure layout copies: XLA must re-lay-out every custom-call operand
# around the [b, h, t, dh] contract, and the b-sized grid pays ~5 us fixed
# cost per program. Here the whole (tq, tk) score fits one kernel program
# (single-block, no online softmax carry) and `bb` batch elements share
# one program, so t <= ~512 runs with 8-32x fewer program invocations and
# zero operand re-layouts. Longer sequences fall back to the K-blocked
# BHTD kernels (one transpose pair) or, beyond that, ring attention.
# ---------------------------------------------------------------------------

_SMALL_T_MAX = 512


def bthd_family(tq, tk, h, dh) -> str:
    """Which implementation [b, t, h, dh] attention takes: "bthd_small"
    (whole tk resident per program), "bthd_kblock" (k walked in blocks,
    tk <= _KB_T_MAX), "bhtd" (one transpose pair into the head-batched
    K-blocked kernels) or "dense" (the jnp composition). A pure function
    of backend and shape, so forward and backward always agree."""
    if not kernels_enabled():
        return "dense"
    # tq is walked in _CQ-row grid steps: a non-dividing tq would
    # truncate nq = tq // cq and leave the tail rows unwritten
    rows_ok = tq >= 8 and (tq <= _CQ or tq % _CQ == 0)
    if rows_ok and tq <= _SMALL_T_MAX and 8 <= tk <= _SMALL_T_MAX:
        return "bthd_small"
    # dk/dv live whole in f32 VMEM scratch: 2 * tk * h * dh * 4 bytes must
    # stay well inside the scoped-vmem budget (h*dh=512, tk=1024 -> 4MB,
    # the measured-safe point; cap at 2x that product). _pick_bk
    # additionally bounds the per-head score temps.
    if (rows_ok and _SMALL_T_MAX < tk <= _KB_T_MAX
            and _pick_bk(tk, h, dh) is not None
            and tk * h * dh <= 2 * 1024 * 512):
        return "bthd_kblock"
    if tk > _SMALL_T_MAX:
        # very long context: dk/dv won't fit VMEM scratch as one piece
        return bhtd_family(h, tq, tk, dh=dh)
    return "dense"


def _small_dropout(seed_ref, i, jc, hi, shape, p_drop):
    """Keep mask (bool) for (batch i, row-block jc, head hi). The callers
    select with it and apply 1/p_keep in float32 where their algebra lets
    it leave the (cq, tk) block. 16-bit random words: RNG throughput is
    bits-bound (uint32 masks measured 0.165 ms/call extra across
    fwd+bwd at b=64 t=256 h=8); 1/65536 keep-rate granularity is far
    below dropout's statistical noise."""
    pltpu.prng_seed(_block_seed(seed_ref[0], i + seed_ref[1], jc, hi))
    p_keep = 1.0 - p_drop
    rows, tk = shape
    if rows % 2 == 0:
        # u32->u16 bitcast doubles the SUBLANE (major) dim: (rows//2, tk)
        # uint32 reinterprets as (rows, tk) uint16. Mosaic can't compare
        # u16 directly, so widen for the compare — the expensive part
        # (random-bit generation) is still halved.
        half = pltpu.prng_random_bits((rows // 2, tk))
        bits = pltpu.bitcast(half, jnp.uint16).astype(jnp.int32)
        thresh = jnp.int32(min(int(p_keep * 65536.0), 65535))
    else:
        bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
        thresh = jnp.uint32(int(p_keep * float(2**32 - 1)))
    return bits < thresh


def _chunked_dropout(seed_ref, i, j, cq, hi, tk, p_drop, key_of_jabs):
    """(cq, tk) keep mask assembled from 128-row sub-blocks keyed by
    ABSOLUTE row-block index (via ``key_of_jabs``), so forward and
    backward kernels regenerate identical streams even when they walk tq
    with different chunk sizes (the forward uses the widest chunk VMEM
    allows; the fused backward runs at 128)."""
    nsub = max(1, cq // _CQ)
    rows = cq // nsub
    subs = [
        _small_dropout(seed_ref, i, key_of_jabs(j * nsub + b), hi,
                       (rows, tk), p_drop)
        for b in range(nsub)
    ]
    return subs[0] if nsub == 1 else jnp.concatenate(subs, axis=0)


def _small_dropout_abs(seed_ref, i, j, cq, hi, tk, p_drop):
    return _chunked_dropout(seed_ref, i, j, cq, hi, tk, p_drop,
                            lambda jabs: jabs)


# Fixed q-chunk for the single-block kernels: tq is walked in _CQ-row grid
# steps with the full tk resident per program (k/v block indices don't
# change with the chunk index, so Pallas skips their re-fetch). Inside a
# program everything is 2-D: heads are LANE slices of the (t, h*dh) view
# (a free minor-dims reshape of the [b, t, h, dh] block), so the kernels
# contain NO vector transposes — Mosaic lowers major-dim transposes to
# element shuffles that measured 4x slower than the whole attention op.
_CQ = 128


def _pick_cq(tq, tk, h):
    """Widest q-chunk that divides tq and keeps the phase-split kernels'
    per-head (cq, tk) f32 temps within Mosaic's scoped-vmem budget (Mosaic
    sums ALL live temps across the unrolled head loop, so the budget
    scales with h). Wider chunks amortize the per-program ramp: the fwd
    kernel measured 0.220 -> 0.152 ms going 128 -> 256 at h=8, tk=256
    (the measured-safe product h*cq*tk anchoring the bound below).
    Dropout streams stay chunk-size-independent via _small_dropout_abs."""
    for c in (256, 128):
        if c <= tq and tq % c == 0 and h * c * tk <= 8 * 256 * 256:
            return c
    return min(tq, _CQ)


def _head(x2, hi, dh):
    return x2[:, hi * dh:(hi + 1) * dh]   # lane slice: (t, dh)


def _times(x, c):
    return x if c == 1.0 else x * c


def _fold_scale(q2, scale):
    """-> (q2, what is left for the scores). A power of two times q is
    exact in q's own type, so the forward kernels apply it to the
    (cq, h*dh) block once and it leaves the h (cq, tk) score blocks; any
    other scale stays on the scores. The products are the same bits
    either way, so the backward kernels may keep the multiply on their
    scores: with q a computed MXU operand they measured a fifth slower
    (PERF.md section 6, PR 49)."""
    if math.frexp(scale)[0] == 0.5:
        return q2 * jnp.asarray(scale, q2.dtype), None
    return q2, scale


def _head_sums(x2, h, dh):
    """[(rows, 1)] * h: x2 (rows, h*dh) summed over each head's lanes,
    on whole 128-lane blocks under a mask: a head of 64 sliced out first
    costs every odd head a lane rotation."""
    sums = []
    for hi in range(h):
        lo, up = hi * dh, (hi + 1) * dh
        a, b = lo // 128 * 128, min(-(-up // 128) * 128, h * dh)
        blk = x2[:, a:b]
        if (a, b) != (lo, up):
            lane = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1) + a
            blk = jnp.where((lane >= lo) & (lane < up), blk, 0.0)
        sums.append(jnp.sum(blk, axis=-1, keepdims=True))
    return sums


def _scores_head(q2, k2, hi, dh, scale, bias_ref, hb, extra_mask=None):
    s = jax.lax.dot_general(
        _head(q2, hi, dh), _head(k2, hi, dh), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                      # (cq, tk)
    if scale is not None:                  # not folded into q
        s = s * scale
    if bias_ref is not None:
        b2 = bias_ref[0, min(hi, hb - 1)]  # (1|cq, tk)
        s = s + b2.astype(jnp.float32)
    if extra_mask is not None:             # causal: True = keep
        s = jnp.where(extra_mask, s, _NEG_INF)
    return s


def _fwd_small_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref,
                      lse_ref, *, scale, p_drop, h, dh, hb):
    # Phase-split over heads (all score matmuls, then all softmaxes, then
    # all pv matmuls): groups the independent per-head matmuls so Mosaic
    # keeps the MXU busy instead of draining it at every head's softmax.
    # Measured 0.220 -> 0.152 ms/call with cq=256 (b=64 t=256 h=8 dh=64).
    # Between a head's two matmuls its (cq, tk) block is passed over for
    # the bias, max, exp, sum and the dropout select alone: the scale is
    # on q, 1 / (l * p_keep) on the (cq, dh) product (PERF.md, PR 49).
    i, j = pl.program_id(0), pl.program_id(1)
    q2, scale = _fold_scale(q_ref[0], scale)
    k2, v2 = k_ref[0], v_ref[0]                 # (cq|tk, h*dh)
    cq, tk = q2.shape[0], k2.shape[0]
    ss = [_scores_head(q2, k2, hi, dh, scale, bias_ref, hb)
          for hi in range(h)]
    ms = [jnp.max(s, axis=-1, keepdims=True) for s in ss]
    ps = [jnp.exp(s - m) for s, m in zip(ss, ms)]
    ls = [jnp.sum(p, axis=-1, keepdims=True) for p in ps]
    # the heads' statistics side by side, (cq, h): a (cq, 1) column a
    # head takes a vreg for every 8 rows and fills one lane of it
    m_all, l_all = (jnp.concatenate(x, axis=-1) for x in (ms, ls))
    r_all = jax.lax.reciprocal(_times(l_all, 1.0 - p_drop))
    lse_ref[0] = m_all + jnp.log(l_all)             # (cq, h)
    if p_drop > 0.0:
        keeps = [_small_dropout_abs(seed_ref, i, j, cq, hi, tk, p_drop)
                 for hi in range(h)]
        ps = [jnp.where(kp, p, 0.0) for kp, p in zip(keeps, ps)]
    outs = [
        jax.lax.dot_general(
            p.astype(v2.dtype), _head(v2, hi, dh), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        for hi, p in enumerate(ps)
    ]
    o_ref[0] = jnp.concatenate(
        [(o * r_all[:, hi:hi + 1]).astype(o_ref.dtype)
         for hi, o in enumerate(outs)], axis=-1)    # (cq, h*dh)


def _bwd_head_grads(q2, k2, v2, do2, out2, lse2, bias_ref, scale, p_drop,
                    h, dh, hb, drop_fn, extra_mask=None):
    """Shared per-head backward phase: recompute scores, p = exp(s - lse),
    dp = do @ v^T and delta = sum(do * out) over the head's lanes, then
    (pds, dss) with the SAME positions dropped from p and dp while dss
    uses the UNdropped p — the invariant both the single-block and
    K-blocked fused backwards must hold. Every factor that is constant
    over the call is left to the caller's (., dh) results: dss lacks
    ``scale / p_keep`` (dq and dk take it), pds ``1 / p_keep`` (dv).
    ``scale`` multiplies the scores here as in the parent: see
    ``_fold_scale``."""
    p_keep = 1.0 - p_drop
    ss = [_scores_head(q2, k2, hi, dh, scale, bias_ref, hb, extra_mask)
          for hi in range(h)]
    ps = [jnp.exp(s - lse2[:, hi:hi + 1]) for hi, s in enumerate(ss)]
    dps = [jax.lax.dot_general(
        _head(do2, hi, dh), _head(v2, hi, dh), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) for hi in range(h)]
    deltas = _head_sums(do2.astype(jnp.float32)
                        * _times(out2.astype(jnp.float32), p_keep), h, dh)
    if p_drop > 0.0:
        keeps = [drop_fn(hi) for hi in range(h)]
        pds = [jnp.where(kp, p, 0.0) for kp, p in zip(keeps, ps)]
        dps = [jnp.where(kp, dp, 0.0) for kp, dp in zip(keeps, dps)]
    else:
        pds = ps
    dss = [p * (dp - d) for p, dp, d in zip(ps, dps, deltas)]
    return pds, dss


def _dqdkv_small_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref,
                        out_ref, lse_ref, dq_ref, dk_ref, dv_ref,
                        dk_scr, dv_scr, *, scale, p_drop, nq, h, dh, hb):
    """Fused backward: one kernel computes dq, dk, dv.

    Separate dq/dkv kernels each recompute the scores s and the dp
    matmul — 7 matmuls total, plus double DMA of q/k/v/do/bias. Fusing
    shares the recompute: 5 matmuls, one operand fetch. Measured
    0.235 + 0.464 -> 0.33 ms/call (b=64 t=256 h=8 dh=64, dropout on).
    Phase-split over heads like the forward. dq writes per (i, j) block;
    dk/dv accumulate in f32 scratch, emitted at the last q-chunk."""
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q2, k2, v2, do2 = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    cq, tk = q2.shape[0], k2.shape[0]
    pds, dss = _bwd_head_grads(
        q2, k2, v2, do2, out_ref[0], lse_ref[0], bias_ref, scale, p_drop,
        h, dh, hb,
        lambda hi: _small_dropout_abs(seed_ref, i, j, cq, hi, tk, p_drop))
    inv_keep = 1.0 / (1.0 - p_drop)
    dqs = [jax.lax.dot_general(
        ds.astype(k2.dtype), _head(k2, hi, dh), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) for hi, ds in enumerate(dss)]
    dq_ref[0] = _times(jnp.concatenate(dqs, axis=-1),
                       scale * inv_keep).astype(dq_ref.dtype)  # (cq, h*dh)
    # dv_h += pd^T @ do_h ; dk_h += ds^T @ q_h   (K = cq, full fill),
    # ONE read-modify-write a scratch as in the K-blocked kernel
    for scr, xs, y2 in ((dv_scr, pds, do2), (dk_scr, dss, q2)):
        scr[...] += jnp.concatenate([jax.lax.dot_general(
            x.astype(y2.dtype), _head(y2, hi, dh), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
            for hi, x in enumerate(xs)], axis=-1)

    @pl.when(j == nq - 1)
    def _emit():
        dk_ref[0] = _times(dk_scr[...],
                           scale * inv_keep).astype(dk_ref.dtype)
        dv_ref[0] = _times(dv_scr[...], inv_keep).astype(dv_ref.dtype)


def _bias_spec_bthd(bias, cq, tk):
    hb, tq_b = bias.shape[1], bias.shape[2]
    if tq_b == 1:
        return pl.BlockSpec((1, hb, 1, tk), lambda i, j, *_: (i, 0, 0, 0))
    return pl.BlockSpec((1, hb, cq, tk), lambda i, j, *_: (i, 0, j, 0))


# ---------------------------------------------------------------------------
# K-blocked BTHD kernels (512 < tk <= _KB_T_MAX): same 2-D lane-sliced head
# layout as the single-block kernels — no [b,h,t,dh] transposes around the
# custom calls (those measured 5.3 ms/step at t=1024) — with the k axis
# walked in _BK-column grid steps and FlashAttention-2 online softmax.
# ---------------------------------------------------------------------------

# Preferred k-block width 512 (fewer online-softmax correction passes:
# measured 166.6k -> 183.2k tok/s at t=1024), falling back to 256 when
# 512 does not divide tk (e.g. tk=768 runs nk=3 blocks of 256). The
# width is a pure function of the shape, so forward and backward always
# agree and the dropout streams stay aligned.
_BK_CHOICES = (512, 256)
_KB_T_MAX = 1024   # dk/dv live whole in f32 scratch: 2 * tk*h*dh*4 bytes


def _pick_bk(tk, h, dh):
    for bk in _BK_CHOICES:
        # the fused backward runs at cq=128 and keeps ~4 (cq, bk) f32
        # temps per head; stay within the measured-safe h*cq*bk product
        if tk % bk == 0 and h * _CQ * bk <= 8 * 256 * 256:
            return bk
    return None


def _kb_dropout(seed_ref, i, j, cq, hi, kk, bk, p_drop):
    """(cq, bk) keep mask for q-chunk j, k-block kk — same absolute
    128-row keying as _small_dropout_abs with the (jabs, kk) pair packed
    into the one mixing slot (nk <= 4 at _KB_T_MAX with bk=256,
    jabs <= 4096)."""
    return _chunked_dropout(seed_ref, i, j, cq, hi, bk, p_drop,
                            lambda jabs: jabs * 4096 + kk)


def _kb_causal_mask(cq, bk, j, kk):
    """(cq, bk) keep-mask for q-chunk j / k-block kk. Forward and
    backward MUST share this (and _causal_live for the dead-block skip)
    or the recomputed backward p diverges from the forward."""
    qpos = jax.lax.broadcasted_iota(jnp.int32, (cq, bk), 0) + j * cq
    kpos = jax.lax.broadcasted_iota(jnp.int32, (cq, bk), 1) + kk * bk
    return qpos >= kpos


def _bias_spec_kb(bias, cq, bk):
    hb, tq_b = bias.shape[1], bias.shape[2]
    if tq_b == 1:
        return pl.BlockSpec((1, hb, 1, bk),
                            lambda i, j, kk, *_: (i, 0, 0, kk))
    return pl.BlockSpec((1, hb, cq, bk),
                        lambda i, j, kk, *_: (i, 0, j, kk))


def _fwd_kb_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                   m_scr, l_scr, acc_scr, *, scale, p_drop, nk, h, dh, hb,
                   bk, causal=False):
    i, j, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute():
        q2, s_scale = _fold_scale(q_ref[0], scale)
        k2, v2 = k_ref[0], v_ref[0]                # (cq, hdh) / (bk, hdh)
        cq = q2.shape[0]
        mask = _kb_causal_mask(cq, bk, j, kk) if causal else None
        # Phase-split with ONE batched read-modify-write of each scratch
        # per program (per-head scratch RMW serialized the loop: measured
        # 0.78 ms/call before, vs 0.087 analytic, at t=1024).
        ss = [_scores_head(q2, k2, hi, dh, s_scale, bias_ref, hb, mask)
              for hi in range(h)]                    # (cq, bk) each
        m_prev = m_scr[...]                          # (cq, h)
        l_prev = l_scr[...]
        m_new = jnp.concatenate(
            [jnp.maximum(m_prev[:, hi:hi + 1],
                         jnp.max(ss[hi], axis=-1, keepdims=True))
             for hi in range(h)], axis=-1)           # (cq, h)
        ps = [jnp.exp(ss[hi] - m_new[:, hi:hi + 1]) for hi in range(h)]
        corr = jnp.exp(m_prev - m_new)               # (cq, h)
        l_scr[...] = l_prev * corr + jnp.concatenate(
            [jnp.sum(p, axis=-1, keepdims=True) for p in ps], axis=-1)
        m_scr[...] = m_new
        if p_drop > 0.0:
            ps = [jnp.where(_kb_dropout(seed_ref, i, j, cq, hi, kk, bk,
                                        p_drop), p, 0.0)
                  for hi, p in enumerate(ps)]
        pv = jnp.concatenate(
            [jax.lax.dot_general(
                p.astype(v2.dtype), _head(v2, hi, dh),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
             for hi, p in enumerate(ps)], axis=-1)   # (cq, hdh)
        corr_full = jnp.concatenate(
            [jnp.broadcast_to(corr[:, hi:hi + 1], (cq, dh))
             for hi in range(h)], axis=-1)
        acc_scr[...] = acc_scr[...] * corr_full + pv

    if causal:
        # fully-future k-blocks contribute nothing: skip their matmuls
        # outright (kk=0 is live for every chunk, so scratch always
        # holds valid running stats before _finish)
        pl.when(_causal_live(j, kk, q_ref.shape[1], bk))(_compute)
    else:
        _compute()

    @pl.when(kk == nk - 1)
    def _finish():
        cq = q_ref.shape[1]
        l_all = l_scr[...]
        recip_full = jnp.concatenate(
            [jnp.broadcast_to(
                jax.lax.reciprocal(l_all[:, hi:hi + 1] * (1.0 - p_drop)),
                (cq, dh)) for hi in range(h)], axis=-1)
        o_ref[0] = (acc_scr[...] * recip_full).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(l_all)


def _dqdkv_kb_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref,
                     out_ref, lse_ref, dq_ref, dk_ref, dv_ref,
                     dq_scr, dk_scr, dv_scr, *, scale, p_drop, nq, nk, h,
                     dh, hb, bk, causal=False):
    """Fused k-blocked backward: dq accumulates over kk per q-chunk;
    dk/dv accumulate into FULL-length (tk, h*dh) f32 scratch across the
    whole (j, kk) walk and are emitted once at the last program."""
    i, j, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init_dq():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(jnp.logical_and(j == 0, kk == 0))
    def _init_dkv():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    inv_keep = 1.0 / (1.0 - p_drop)

    def _compute():
        q2, k2, v2, do2 = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        cq = q2.shape[0]
        mask = _kb_causal_mask(cq, bk, j, kk) if causal else None
        pds, dss = _bwd_head_grads(
            q2, k2, v2, do2, out_ref[0], lse_ref[0], bias_ref, scale,
            p_drop, h, dh, hb,
            lambda hi: _kb_dropout(seed_ref, i, j, cq, hi, kk, bk, p_drop),
            extra_mask=mask)
        # Batched scratch RMW: one load+store per scratch per program
        # instead of per head (per-head RMW serializes against the
        # matmuls).
        dq_scr[...] += jnp.concatenate(
            [jax.lax.dot_general(
                ds.astype(k2.dtype), _head(k2, hi, dh),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
             for hi, ds in enumerate(dss)], axis=-1)
        rows = pl.ds(kk * bk, bk)
        dv_scr[rows, :] += jnp.concatenate(
            [jax.lax.dot_general(
                pd.astype(do2.dtype), _head(do2, hi, dh),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
             for hi, pd in enumerate(pds)], axis=-1)
        dk_scr[rows, :] += jnp.concatenate(
            [jax.lax.dot_general(
                ds.astype(q2.dtype), _head(q2, hi, dh),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
             for hi, ds in enumerate(dss)], axis=-1)

    if causal:
        pl.when(_causal_live(j, kk, q_ref.shape[1], bk))(_compute)
    else:
        _compute()

    @pl.when(kk == nk - 1)
    def _emit_dq():
        dq_ref[0] = _times(dq_scr[...],
                           scale * inv_keep).astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(j == nq - 1, kk == nk - 1))
    def _emit_dkv():
        dk_ref[0] = _times(dk_scr[...],
                           scale * inv_keep).astype(dk_ref.dtype)
        dv_ref[0] = _times(dv_scr[...], inv_keep).astype(dv_ref.dtype)


def _bthd_kb_fwd(q, k, v, bias, seed, scale, p_drop, causal=False):
    b, tq, h, dh = q.shape
    tk = k.shape[1]
    bk = _pick_bk(tk, h, dh)
    cq = _pick_cq(tq, bk, h)
    nq, nk = tq // cq, tk // bk
    hdh = h * dh
    in_specs = [
        pl.BlockSpec((1, cq, hdh), lambda i, j, kk, *_: (i, j, 0)),
        pl.BlockSpec((1, bk, hdh), lambda i, j, kk, *_: (i, kk, 0)),
        pl.BlockSpec((1, bk, hdh), lambda i, j, kk, *_: (i, kk, 0)),
    ]
    args = [q.reshape(b, tq, hdh), k.reshape(b, tk, hdh),
            v.reshape(b, tk, hdh)]
    hb = 1 if bias is None else bias.shape[1]
    if bias is not None:
        in_specs.append(_bias_spec_kb(bias, cq, bk))
        args.append(bias)
        kernel = functools.partial(_fwd_kb_kernel, scale=scale,
                                   p_drop=p_drop, nk=nk, h=h, dh=dh, hb=hb,
                                   bk=bk, causal=causal)
    else:
        kernel = functools.partial(
            lambda sr, qr, kr, vr, orf, lr, ms, ls, ac, **kw:
                _fwd_kb_kernel(sr, qr, kr, vr, None, orf, lr, ms, ls, ac,
                               **kw),
            scale=scale, p_drop=p_drop, nk=nk, h=h, dh=dh, hb=hb, bk=bk,
            causal=causal,
        )
    operands = (_seed_arr(seed), *args)
    out2, lse2 = pl.pallas_call(
        kernel, name="attn.bthd_kblock.fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nq, nk),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, cq, hdh), lambda i, j, kk, *_: (i, j, 0)),
                pl.BlockSpec((1, cq, h), lambda i, j, kk, *_: (i, j, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((cq, h), jnp.float32),
                pltpu.VMEM((cq, h), jnp.float32),
                pltpu.VMEM((cq, hdh), jnp.float32),
            ],
        ),
        out_shape=[
            _result(operands, (b, tq, hdh), q.dtype),
            _result(operands, (b, tq, h), jnp.float32),
        ],
        interpret=_INTERPRET,
    )(*operands)
    return out2.reshape(b, tq, h, dh), lse2[..., None]


def _bthd_kb_bwd(q, k, v, bias, seed, out, lse, g, scale, p_drop,
                 causal=False):
    b, tq, h, dh = q.shape
    tk = k.shape[1]
    bk = _pick_bk(tk, h, dh)
    cq = min(_pick_cq(tq, bk, h), _CQ)
    nq, nk = tq // cq, tk // bk
    hdh = h * dh
    base_specs = [
        pl.BlockSpec((1, cq, hdh), lambda i, j, kk, *_: (i, j, 0)),
        pl.BlockSpec((1, bk, hdh), lambda i, j, kk, *_: (i, kk, 0)),
        pl.BlockSpec((1, bk, hdh), lambda i, j, kk, *_: (i, kk, 0)),
    ]
    base_args = [q.reshape(b, tq, hdh), k.reshape(b, tk, hdh),
                 v.reshape(b, tk, hdh)]
    hb = 1 if bias is None else bias.shape[1]
    if bias is not None:
        base_specs.append(_bias_spec_kb(bias, cq, bk))
        base_args.append(bias)
    tail_specs = [
        pl.BlockSpec((1, cq, hdh), lambda i, j, kk, *_: (i, j, 0)),   # do
        pl.BlockSpec((1, cq, hdh), lambda i, j, kk, *_: (i, j, 0)),   # out
        pl.BlockSpec((1, cq, h), lambda i, j, kk, *_: (i, j, 0)),     # lse
    ]
    tail_args = [g.reshape(b, tq, hdh), out.reshape(b, tq, hdh),
                 lse[..., 0]]
    if bias is not None:
        kernel = functools.partial(_dqdkv_kb_kernel, scale=scale,
                                   p_drop=p_drop, nq=nq, nk=nk, h=h, dh=dh,
                                   hb=hb, bk=bk, causal=causal)
    else:
        kernel = functools.partial(
            lambda sr, qr, kr, vr, dor, outr, lr, dqr, dkr, dvr, dqs, dks,
            dvs, **kw: _dqdkv_kb_kernel(sr, qr, kr, vr, None, dor, outr, lr,
                                        dqr, dkr, dvr, dqs, dks, dvs, **kw),
            scale=scale, p_drop=p_drop, nq=nq, nk=nk, h=h, dh=dh, hb=hb,
            bk=bk, causal=causal,
        )
    operands = (_seed_arr(seed), *base_args, *tail_args)
    dq2, dk2, dv2 = pl.pallas_call(
        kernel, name="attn.bthd_kblock.bwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nq, nk),
            in_specs=base_specs + tail_specs,
            out_specs=[
                pl.BlockSpec((1, cq, hdh), lambda i, j, kk, *_: (i, j, 0)),
                pl.BlockSpec((1, tk, hdh), lambda i, j, kk, *_: (i, 0, 0)),
                pl.BlockSpec((1, tk, hdh), lambda i, j, kk, *_: (i, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((cq, hdh), jnp.float32),
                pltpu.VMEM((tk, hdh), jnp.float32),
                pltpu.VMEM((tk, hdh), jnp.float32),
            ],
        ),
        out_shape=[
            _result(operands, (b, tq, hdh), q.dtype),
            _result(operands, (b, tk, hdh), k.dtype),
            _result(operands, (b, tk, hdh), v.dtype),
        ],
        # The fused kb backward's phase temps land at ~16.7M of Mosaic
        # scoped-vmem stack when compiled inside a run_steps While body
        # on the current toolchain (16.0M default limit; it fits
        # standalone). 24M is still a small fraction of the v5e's 128M
        # VMEM and keeps cq=128 (halving cq would double the dq-scratch
        # RMW passes on the t=1024 headline config).
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=24 * 1024 * 1024),
        interpret=_INTERPRET,
    )(*operands)
    return (dq2.reshape(b, tq, h, dh), dk2.reshape(b, tk, h, dh),
            dv2.reshape(b, tk, h, dh))


def bthd_dropout_masks(b, tq, tk, h, dh, p_drop, seed):
    """The BTHD kernels' scaled keep masks (keep / p_keep) as one
    [b, tq, h, tk] f32 array, regenerated on the device by the kernels'
    OWN helpers and keys. A dense reference fed these masks must agree
    with the kernels (the hardware tests and chip_smoke.py use it);
    needs the TPU PRNG."""
    family = bthd_family(tq, tk, h, dh)
    if family not in ("bthd_small", "bthd_kblock"):
        raise ValueError(f"no BTHD dropout stream for family '{family}'")
    cq = min(tq, _CQ)

    def kern(seed_ref, o_ref):
        i, j = pl.program_id(0), pl.program_id(1)
        for hi in range(h):
            if family == "bthd_kblock":
                bk = _pick_bk(tk, h, dh)
                m = jnp.concatenate(
                    [_kb_dropout(seed_ref, i, j, cq, hi, kk, bk, p_drop)
                     for kk in range(tk // bk)], axis=-1)
            else:
                m = _small_dropout_abs(seed_ref, i, j, cq, hi, tk, p_drop)
            o_ref[0, :, hi * tk:(hi + 1) * tk] = jnp.where(
                m, jnp.float32(1.0 / (1.0 - p_drop)), 0.0)

    operands = (_seed_arr(seed),)
    out = pl.pallas_call(
        kern, name=f"attn.{family}.dropout_masks",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, tq // cq), in_specs=[],
            out_specs=pl.BlockSpec((1, cq, h * tk),
                                   lambda i, j, *_: (i, j, 0))),
        out_shape=_result(operands, (b, tq, h * tk), jnp.float32),
        interpret=_INTERPRET,
    )(*operands)
    return out.reshape(b, tq, h, tk)


def _combined_causal_bias(bias, tq, tk):
    """Fold the causal future-mask into an additive bias for the BTHD
    small/k-blocked kernels (t <= 1024 there, so the [tq, tk] tensor is
    bounded at ~4MB and XLA CSEs the pure computation across layers).
    The long-context BHTD kernels never take this path — they get the
    in-kernel position mask instead."""
    tri = jnp.where(
        jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :],
        jnp.float32(0), jnp.float32(_NEG_INF))[None, None]
    return tri if bias is None else bias.astype(jnp.float32) + tri


def _reference_attention_bthd(q, k, v, bias, scale, p_drop=0.0, seed=None):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias.astype(s.dtype)
    p = jax.nn.softmax(s, axis=-1)
    if p_drop > 0.0:
        key = jax.random.PRNGKey(0 if seed is None else jnp.asarray(seed))
        keep = jax.random.bernoulli(key, 1.0 - p_drop, p.shape)
        p = jnp.where(keep, p / (1.0 - p_drop), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def flash_attention_bthd_fwd(q, k, v, bias=None, seed=None, scale=None,
                             p_drop: float = 0.0, causal: bool = False):
    """q [b, tq, h, dh], k/v [b, tk, h, dh] -> (out [b, tq, h, dh],
    lse [b, tq, h, 1] f32; zeros on the dense fallback). ``causal``:
    in-kernel future mask on the long-context BHTD path (no [tq, tk]
    tensor, dead blocks skipped); folded into a bounded bias on the
    t <= 1024 small/k-blocked paths."""
    if p_drop > 0.0 and seed is None:
        raise ValueError("flash_attention: p_drop > 0 requires `seed`")
    b, tq, h, dh = q.shape
    tk = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    family = bthd_family(tq, tk, h, dh)
    if family == "bhtd":
        # causal rides the in-kernel mask + block skip
        out, lse = flash_attention_fwd(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2), bias, seed, scale, p_drop,
            causal=causal)
        return jnp.swapaxes(out, 1, 2), jnp.swapaxes(lse, 1, 2)
    if family == "bthd_kblock":
        return _bthd_kb_fwd(q, k, v, bias, seed, scale, p_drop,
                            causal=causal)
    if causal:
        bias = _combined_causal_bias(bias, tq, tk)
    if family == "dense":
        out = _reference_attention_bthd(q, k, v, bias, scale, p_drop,
                                        seed if p_drop > 0.0 else None)
        return out, jnp.zeros((b, tq, h, 1), jnp.float32)

    cq = _pick_cq(tq, tk, h)
    nq = tq // cq
    hdh = h * dh
    in_specs = [
        pl.BlockSpec((1, cq, hdh), lambda i, j, *_: (i, j, 0)),
        pl.BlockSpec((1, tk, hdh), lambda i, j, *_: (i, 0, 0)),
        pl.BlockSpec((1, tk, hdh), lambda i, j, *_: (i, 0, 0)),
    ]
    args = [q.reshape(b, tq, hdh), k.reshape(b, tk, hdh),
            v.reshape(b, tk, hdh)]
    hb = 1 if bias is None else bias.shape[1]
    if bias is not None:
        in_specs.append(_bias_spec_bthd(bias, cq, tk))
        args.append(bias)
        kernel = functools.partial(_fwd_small_kernel, scale=scale,
                                   p_drop=p_drop, h=h, dh=dh, hb=hb)
    else:
        kernel = functools.partial(
            lambda sr, qr, kr, vr, orf, lr, **kw: _fwd_small_kernel(
                sr, qr, kr, vr, None, orf, lr, **kw),
            scale=scale, p_drop=p_drop, h=h, dh=dh, hb=hb,
        )
    operands = (_seed_arr(seed), *args)
    out2, lse2 = pl.pallas_call(
        kernel, name="attn.bthd_small.fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nq),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, cq, hdh), lambda i, j, *_: (i, j, 0)),
                pl.BlockSpec((1, cq, h), lambda i, j, *_: (i, j, 0)),
            ],
        ),
        out_shape=[
            _result(operands, (b, tq, hdh), q.dtype),
            _result(operands, (b, tq, h), jnp.float32),
        ],
        interpret=_INTERPRET,
    )(*operands)
    return out2.reshape(b, tq, h, dh), lse2[..., None]


def flash_attention_bthd_bwd(q, k, v, bias, seed, out, lse, g, scale=None,
                             p_drop: float = 0.0, causal: bool = False):
    """-> (dq, dk, dv) in [b, t, h, dh], consuming the forward's saved
    (out, lse). ``causal`` routes exactly as the forward did, so the
    recomputed p matches block for block."""
    b, tq, h, dh = q.shape
    tk = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    family = bthd_family(tq, tk, h, dh)
    if family == "bhtd":
        dq, dk, dv = flash_attention_bwd(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2), bias, seed,
            jnp.swapaxes(out, 1, 2), jnp.swapaxes(lse, 1, 2),
            jnp.swapaxes(g, 1, 2), scale, p_drop, causal=causal)
        return (jnp.swapaxes(dq, 1, 2), jnp.swapaxes(dk, 1, 2),
                jnp.swapaxes(dv, 1, 2))
    if family == "bthd_kblock":
        return _bthd_kb_bwd(q, k, v, bias, seed, out, lse, g, scale,
                            p_drop, causal=causal)
    if causal:
        bias = _combined_causal_bias(bias, tq, tk)
    if family == "dense":
        def f(q, k, v):
            return _reference_attention_bthd(
                q, k, v, bias, scale, p_drop,
                seed if p_drop > 0.0 else None)

        _, vjp = jax.vjp(f, q, k, v)
        return vjp(g)

    # The forward's chunk: at tq = 256 ONE step a batch row, so dk and dv
    # are gathered once (two steps of 128 rows count 8596 bundles of
    # libtpu's schedule a row, one of 256 counts 7240: PERF.md section 6,
    # PR 49). It fits Mosaic's default scoped VMEM, alone and inside a
    # While body, and a higher limit only slows it: 2% at 32M, 6% at 64M
    # (same section). Dropout streams are chunk-independent.
    cq = _pick_cq(tq, tk, h)
    nq = tq // cq
    hdh = h * dh
    base_specs = [
        pl.BlockSpec((1, cq, hdh), lambda i, j, *_: (i, j, 0)),   # q
        pl.BlockSpec((1, tk, hdh), lambda i, j, *_: (i, 0, 0)),   # k
        pl.BlockSpec((1, tk, hdh), lambda i, j, *_: (i, 0, 0)),   # v
    ]
    base_args = [q.reshape(b, tq, hdh), k.reshape(b, tk, hdh),
                 v.reshape(b, tk, hdh)]
    if bias is not None:
        base_specs = base_specs + [_bias_spec_bthd(bias, cq, tk)]
        base_args = base_args + [bias]
    tail_specs = [
        pl.BlockSpec((1, cq, hdh), lambda i, j, *_: (i, j, 0)),   # do
        pl.BlockSpec((1, cq, hdh), lambda i, j, *_: (i, j, 0)),   # out
        pl.BlockSpec((1, cq, h), lambda i, j, *_: (i, j, 0)),     # lse
    ]
    # delta = sum(do * out) is made in the kernel from the two blocks it
    # holds: in XLA it was a float32 pass over [b, tq, h, dh] and a
    # [b, tq, h] result with h on the lanes (PERF.md, PR 49)
    tail_args = [g.reshape(b, tq, hdh), out.reshape(b, tq, hdh),
                 lse[..., 0]]

    hb = 1 if bias is None else bias.shape[1]
    if bias is not None:
        kernel = functools.partial(_dqdkv_small_kernel, scale=scale,
                                   p_drop=p_drop, nq=nq, h=h, dh=dh, hb=hb)
    else:
        kernel = functools.partial(
            lambda sr, qr, kr, vr, dor, outr, lr, dqr, dkr, dvr, dks, dvs,
            **kw: _dqdkv_small_kernel(sr, qr, kr, vr, None, dor, outr, lr,
                                      dqr, dkr, dvr, dks, dvs, **kw),
            scale=scale, p_drop=p_drop, nq=nq, h=h, dh=dh, hb=hb,
        )

    operands = (_seed_arr(seed), *base_args, *tail_args)
    dq2, dk2, dv2 = pl.pallas_call(
        kernel, name="attn.bthd_small.bwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nq),
            in_specs=base_specs + tail_specs,
            out_specs=[
                pl.BlockSpec((1, cq, hdh), lambda i, j, *_: (i, j, 0)),
                pl.BlockSpec((1, tk, hdh), lambda i, j, *_: (i, 0, 0)),
                pl.BlockSpec((1, tk, hdh), lambda i, j, *_: (i, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((tk, hdh), jnp.float32),
                pltpu.VMEM((tk, hdh), jnp.float32),
            ],
        ),
        out_shape=[
            _result(operands, (b, tq, hdh), q.dtype),
            _result(operands, (b, tk, hdh), k.dtype),
            _result(operands, (b, tk, hdh), v.dtype),
        ],
        interpret=_INTERPRET,
    )(*operands)
    return (dq2.reshape(b, tq, h, dh), dk2.reshape(b, tk, h, dh),
            dv2.reshape(b, tk, h, dh))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def flash_attention_bthd_with_lse(q, k, v, bias=None, seed=None,
                                  scale: Optional[float] = None,
                                  p_drop: float = 0.0,
                                  causal: bool = False):
    """(out, lse) in BTHD with a custom vjp over the single-block kernels
    (pallas_call has no JVP rule); the paired sdpa grad op uses the _bwd
    entry directly with the saved stats.

    ``bias`` is mask plumbing, NOT a trainable input: on the Pallas paths
    its cotangent is ZEROS (a true dbias would materialize a tq x tk
    gradient per head). Pass a learnable additive bias only through the
    dense composition (small shapes), which computes the real dbias."""
    return flash_attention_bthd_fwd(q, k, v, bias, seed, scale, p_drop,
                                    causal)


def _bthd_vjp_fwd(q, k, v, bias, seed, scale, p_drop, causal=False):
    out, lse = flash_attention_bthd_fwd(q, k, v, bias, seed, scale, p_drop,
                                        causal)
    return (out, lse), (q, k, v, bias, seed, out, lse)


def _bthd_vjp_bwd(scale, p_drop, causal, res, gs):
    g, _g_lse = gs
    q, k, v, bias, seed, out, lse = res
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    _, tq_, h, dh = q.shape
    tk_ = k.shape[1]
    if bthd_family(tq_, tk_, h, dh) != "dense":
        dq, dk, dv = flash_attention_bthd_bwd(
            q, k, v, bias, seed, out, lse, g.astype(q.dtype), scale, p_drop,
            causal)
        dbias = None if bias is None else jnp.zeros_like(bias)
    else:
        sd = seed if p_drop > 0.0 else None
        if bias is None:
            # the causal fold is a constant here — fold it outside vjp
            eff_bias = (_combined_causal_bias(None, tq_, tk_)
                        if causal else None)
            _, vjp = jax.vjp(
                lambda a, b, c: _reference_attention_bthd(
                    a, b, c, eff_bias, scale, p_drop, sd), q, k, v)
            dq, dk, dv = vjp(g.astype(q.dtype))
            dbias = None
        else:
            # bias is differentiated: the fold must happen INSIDE the
            # vjp'd function so dbias reflects only the caller's bias
            _, vjp = jax.vjp(
                lambda a, b, c, bb_: _reference_attention_bthd(
                    a, b, c,
                    _combined_causal_bias(bb_, tq_, tk_) if causal
                    else bb_,
                    scale, p_drop, sd), q, k, v, bias)
            dq, dk, dv, dbias = vjp(g.astype(q.dtype))
    return dq, dk, dv, dbias, _seed_cotangent(seed)


flash_attention_bthd_with_lse.defvjp(_bthd_vjp_fwd, _bthd_vjp_bwd)
