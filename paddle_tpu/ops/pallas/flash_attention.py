"""Flash attention as Pallas TPU kernels (forward + backward).

The reference's only fused attention is the inference-only CUDA
``multihead_matmul`` (paddle/fluid/operators/fused/multihead_matmul_op.cc:118);
its training attention materializes the full [b, h, s, s] probability
tensor (python/paddle/nn/layer/transformer.py:68). This module is the
TPU-native replacement: O(s) memory attention with online softmax in the
forward and a recomputing two-kernel backward (dq-kernel gridded over q
blocks; dk/dv-kernel gridded over k blocks), so nothing quadratic ever
touches HBM. Inputs may be bf16; all accumulation is fp32 on the MXU.

Layout: the kernels take q/k/v as [batch*heads, seq, head_dim]; the
public entry accepts [b, h, s, d] and collapses the leading axes into the
grid's first dim (per chip, when the program spans several).
The only saved residuals are (o, lse) — the backward recomputes the
probabilities blockwise, the standard flash-attention trade. The vjp
names the pair (``utils.FLASH_RESIDUAL_NAMES``); a recompute segment
(fleet.utils.recompute) keeps them beside its input, so its backward
rebuilds q, k and v from that input but does not run the forward kernel
again. Outside a checkpoint the names are the identity.

**The block plan.** How a call's logits are cut into grid steps and tiles
is decided by :func:`block_plan`, a pure function of what the call can see
(the two sequence lengths, ``d``, ``causal``, ``window``); nothing sets it
but the shapes, and ``block_q`` / ``block_k`` are the largest tile it may
take. All three kernels walk the same band of tiles (the dk/dv kernel its
mirror image):

* a tile that lies wholly between the causal edge and the window's edge
  is *interior*: it is computed whole (``block_q x block_k``, the size
  that feeds the matrix unit) and takes **no mask** (a ``where`` whose
  every ``keep`` is true is the identity, so no value moves);
* a tile an edge crosses is masked whole by the forward and the dk/dv
  kernel, as all three did. With blocks of 512 a causal call at s 1024
  makes 3 tiles of logits for the 2 it needs (67% live,
  :meth:`BlockPlan.visited_share`) and a window of 512 makes 2 for barely
  1 (50%): block-skipping halves causal work only where the sequence is
  many blocks long. The dq kernel cuts such a tile in four, again and
  again, down to sub-tiles of ``dq_sub`` on a side: a sub-tile with no
  live key is **not computed**, one that an edge still crosses is masked,
  and one that has come clear of both edges is computed bare at the
  largest size it came clear at. Quarters of 256 make the shares above
  80% and 67%. A piece has costs of its own (its operands' loads and
  casts, its products' way in and out of the matrix unit, in the forward
  one more pass over the softmax's state), so on the v5e quarters gain in
  the dq kernel alone, and sub-tiles of 128 nowhere
  (``tools/flash_block_plans.py``; ``perfbench/study/runs_pr59.jsonl``;
  ``PERF.md`` section 6, PR 60, which landed the plan of the refused PR
  59);
* a grid step's turn costs about what a tile does, so a step holds more
  than one where that pays: a sequence of two tiles (s 1024) is ONE step
  in all three kernels, and its walk is then known when the kernel is
  traced and has no loop in it; of a longer sequence the dq kernel takes
  up to four query tiles a step, as many as leave its step a MiB under
  the compiler's default VMEM limit, and the other two, which gained 2-3%
  by that, keep one, because a step's tiles are unrolled and a served
  model lowers the forward once a layer and a bucket.

**What a start pays for a kernel.** A step of ``n`` layers calls each
kernel ``n`` times with one shape. The three calls are traced ONCE a shape
(:func:`_traced_once`: a ``jax.jit`` around each, everything but the
arrays static), so the step's module holds each kernel's Mosaic payload
once and every layer calls the function around it: a kernel's own text is a
third longer than it was before the plan (31.8 k characters against 24.0 k
for ``jax.grad`` of one call at GPT's shape; the unrolled whole-sequence
step is most of it), and the module of a step of many layers is shorter
than it was. XLA inlines the calls, so the compiled step is unchanged: one
custom call a kernel a layer, under the kernel's name. The payload holds
the Python stack the kernel was traced under (file paths and lines), which
is why the persistent cache's keys differ from one checkout's path to
another's (``tests/test_chip_compile.py`` lowers twice from one line).

Sub-tiling changes the ORDER in which a row's keys are accumulated (in
the dq kernel, and only where an edge crosses: interior tiles run first to
last as before), so dq moves in the last bits of a float32 sum and no
further.

A sliding window (``window``: query i sees keys ``i-window+1 .. i``) is
one more edge of the same walk. Grouped KV heads (fewer k/v heads than q
heads) are read through the index map (query head ``j`` reads KV head
``j // group``); the dk/dv kernel writes one partial per query head and
the group is summed outside it. ``tag`` names the three kernels
(``flash_fwd_<tag>`` ...), so that a program whose layers differ in head
count or key range has one shape under each name. Each is ONE
``pallas_call`` a layer call whatever the plan.

On a CPU backend (tests, virtual meshes) the kernels run in Pallas
interpreter mode, so the same code path is exercised everywhere.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .utils import (FLASH_RESIDUAL_NAMES, LANE, interpret_mode as _interpret,
                    pad_lane_dim, pick_block, shard_parallel)

NEG_INF = float("-inf")
#: the compiler's default scoped VMEM limit: a kernel whose step
#: (:func:`fwd_vmem_bytes`, :func:`bwd_dq_vmem_bytes`,
#: :func:`bwd_dkv_vmem_bytes`) fits it is compiled with no limit of its
#: own; past it the call asks for one, while one head's K and V stay under
#: :data:`_FWD_KV_MOST` (the chip has 128 MiB)
_VMEM_DEFAULT = 16 << 20
_FWD_KV_MOST = 64 << 20
#: what a dq step of several tiles leaves free under that default by its
#: estimate: at float32 products of `highest` precision the compiler took
#: 0.4 MiB more than the estimate says (s 8192, d 128, four tiles: PR 59)
_DQ_STEP_ROOM = 1 << 20


# ------------------------------------------------------------------- plan

class Piece(NamedTuple):
    """A square of logits the walk computes: ``n`` major positions from
    ``major`` and ``n`` minor positions from ``minor``, both relative to
    the first position of the major tile; ``cut_lo`` / ``cut_hi`` say
    which edge of the band still crosses it (it is masked there)."""
    major: int
    minor: int
    n: int
    cut_lo: bool
    cut_hi: bool


def _classify(major, minor, n, lo, hi):
    """(dead, cut_lo, cut_hi) of the square against the band ``lo <=
    minor - major <= hi`` (``None``: no such edge)."""
    u_min, u_max = minor - (major + n - 1), minor + n - 1 - major
    dead = (hi is not None and u_min > hi) or (lo is not None and u_max < lo)
    return (dead, lo is not None and u_min < lo,
            hi is not None and u_max > hi)


def _split(major, minor, n, sub, lo, hi, out):
    dead, cut_lo, cut_hi = _classify(major, minor, n, lo, hi)
    if dead:
        return
    if not (cut_lo or cut_hi) or n <= sub:
        out.append(Piece(major, minor, n, cut_lo, cut_hi))
        return
    h = n // 2
    for dm in (0, h):
        for dn in (0, h):
            _split(major + dm, minor + dn, h, sub, lo, hi, out)


@functools.lru_cache(maxsize=None)
def _walk(tile, sub, lo, hi):
    """The minor tiles one major tile meets, by their index relative to
    its own: ``(run, edges)``. ``run = (t_lo, t_hi)`` are the tiles wholly
    inside the band (``None``: to that end of the sequence; ``t_lo ==
    t_hi``: none); ``edges = ((t, pieces), ...)`` are the tiles an edge of
    the band crosses, each with the pieces it is walked in, in the order
    of ``t``."""
    if lo is None and hi is None:
        return (None, None), ()
    # the tiles that are not dead: t*tile + tile - 1 >= lo and t*tile -
    # (tile - 1) <= hi; three more on an open side show what goes on for
    # ever there
    first = -((tile - 1 - lo) // tile) if lo is not None \
        else (hi + tile - 1) // tile - 3
    last = (hi + tile - 1) // tile if hi is not None else first + 3
    full, edges = [], []
    for t in range(first, last + 1):
        dead, cut_lo, cut_hi = _classify(0, t * tile, tile, lo, hi)
        assert not dead, (tile, lo, hi, t)
        if cut_lo or cut_hi:
            pieces = []
            _split(0, t * tile, tile, sub, lo, hi, pieces)
            edges.append((t, tuple(pieces)))
        else:
            full.append(t)
    # an open side always has whole tiles; they are one run
    assert full or None not in (lo, hi), (tile, lo, hi)
    assert full == list(range(full[0], full[-1] + 1)) if full else True
    t_lo, t_hi = (full[0], full[-1] + 1) if full else (edges[0][0],) * 2
    return ((None if lo is None else t_lo, None if hi is None else t_hi),
            tuple(edges))


#: the three kernels, as :class:`BlockPlan` numbers them
FWD, DQ, DKV = range(3)


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """How the three kernels cut a call into grid steps and tiles.

    ``block_q`` / ``block_k``: rows and keys of a tile (equal under
    ``causal``). ``dq_sub``: side of the smallest sub-tile the dq kernel
    walks an edge tile in (the tile's own side: masked whole, as the other
    two kernels always do). ``dq_tiles``: query tiles a dq grid step
    holds. ``tiles``: query tiles a forward step and key tiles a dk/dv
    step holds (1, or both tiles of a sequence of two)."""
    block_q: int
    block_k: int
    dq_sub: int
    dq_tiles: int = 1
    tiles: int = 1

    def walk(self, kernel, causal, window):
        """:func:`_walk` of the forward / dq kernels' band (keys behind the
        query: ``-(window-1) <= key - query <= 0``) or of the dk/dv
        kernel's, its mirror image (queries ahead of the key)."""
        sub = self.dq_sub if kernel == DQ else self.block_q
        if not causal:
            return _walk(self.block_q, sub, None, None)
        assert self.block_q == self.block_k, self
        far = window - 1 if window else None
        lo, hi = (0, far) if kernel == DKV \
            else (None if far is None else -far, 0)
        return _walk(self.block_q, sub, lo, hi)

    def visited_products(self, seq_q, seq_k, causal, window=0, kernel=DQ):
        """Logits of one head that ``kernel`` computes under this plan."""
        window = _effective_window(window, seq_k)
        (t_lo, t_hi), edges = self.walk(kernel, causal, window)
        n_major, n_minor = seq_q // self.block_q, seq_k // self.block_k
        if kernel == DKV:
            n_major, n_minor = n_minor, n_major
        total = 0
        for j in range(n_major):
            lo = 0 if t_lo is None else max(j + t_lo, 0)
            hi = n_minor if t_hi is None else min(j + t_hi, n_minor)
            total += max(hi - lo, 0) * self.block_q * self.block_k
            total += sum(p.n * p.n for t, pieces in edges
                         if 0 <= j + t < n_minor for p in pieces)
        return total

    def visited_share(self, seq_q, seq_k, causal, window=0, kernel=DQ):
        """Live logits (those the mask and the window leave) over the
        logits ``kernel`` computes: how far the plan engages there, a
        number of the shape alone."""
        return live_products(seq_q, seq_k, causal, window) \
            / self.visited_products(seq_q, seq_k, causal, window, kernel)


def live_products(seq_q, seq_k, causal, window=0):
    """Logits of one head that the mask and the window leave."""
    if not causal:
        return seq_q * seq_k
    window = _effective_window(window, seq_k) or seq_k
    full_rows = max(seq_q - window, 0)
    ramp = seq_q - full_rows
    return full_rows * window + ramp * (ramp + 1) // 2


def _effective_window(window, seq_k):
    """A window that reaches the whole sequence is no edge."""
    return 0 if not window or window >= seq_k else int(window)


def block_plan(seq_q, seq_k, d, causal, window=0, block_q=512,
               block_k=512, dtype=jnp.bfloat16):
    """The :class:`BlockPlan` of a call from its shape, or ``None`` where
    the sequences cannot be tiled. Its rules are what ``tools/
    flash_block_plans.py`` read on the v5e (PR 59's runs, landed at PR 60;
    ``PERF.md`` section 6).
    """
    bq = pick_block(seq_q, block_q, minimum=16)
    bk = pick_block(seq_k, block_k, minimum=16)
    if not bq or not bk:
        return None
    if not causal:
        return BlockPlan(bq, bk, max(bq, bk))
    bq = bk = min(bq, bk)
    # the dq kernel walks an edge tile in quarters where a quarter is two
    # lane tiles or more
    dq_sub = bq // 2 if bq >= 4 * LANE else bq
    # a grid step's turn costs about a tile's time. A sequence of two
    # tiles is one step in all three kernels: its walk is then known when
    # the kernel is traced and has no loop in it. Of a longer one the dq
    # kernel takes up to four tiles a step, as many as leave the step
    # clearly under the default VMEM limit; the other two gain 2-3% by
    # that, which does not pay for a program four times as long to lower
    # (a served model lowers the forward once a layer and a bucket)
    n = seq_q // bq
    if n <= 2:
        return BlockPlan(bq, bk, dq_sub, n, n)
    fits = _VMEM_DEFAULT - _DQ_STEP_ROOM
    dq_tiles = next(t for t in (4, 3, 2, 1) if t == 1 or (
        n % t == 0
        and bwd_dq_vmem_bytes(seq_k, pad_lane_dim(d), dtype, bq, bk,
                              t) <= fits))
    return BlockPlan(bq, bk, dq_sub, dq_tiles)


# ---------------------------------------------------------------- kernels

def _band_mask(s, delta, piece, lo, hi, minor_axis):
    """Mask the logits of one piece by the edges that cross it: an element
    at (major i, minor j) of the piece is kept where ``lo <= delta + j - i
    <= hi``."""
    u = delta + jax.lax.broadcasted_iota(jnp.int32, s.shape, minor_axis) \
        - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - minor_axis)
    keep = None
    if piece.cut_hi:
        keep = u <= hi
    if piece.cut_lo:
        keep = u >= lo if keep is None else jnp.logical_and(keep, u >= lo)
    return jnp.where(keep, s, NEG_INF)


def _qk(q, k):
    return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tn(a, b):
    """``a^T b`` over the leading axis of both."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _static(x):
    return isinstance(x, int)


def _at_least(x, lo):
    return max(x, lo) if _static(x) else jnp.maximum(x, lo)


def _at_most(x, hi):
    return min(x, hi) if _static(x) else jnp.minimum(x, hi)


def _loop(lo, hi, body, carry):
    """``carry = body(i, carry)`` for ``i`` in ``[lo, hi)``; unrolled where
    both ends are known when the kernel is traced."""
    if _static(lo) and _static(hi):
        for i in range(lo, hi):
            carry = body(i, carry)
        return carry
    return jax.lax.fori_loop(lo, hi, body, carry)


def _when(cond, body, carry):
    """``body(carry)`` where ``cond`` holds, else ``carry``: decided when
    the kernel is traced if it can be."""
    if isinstance(cond, bool):
        return body(carry) if cond else carry
    return jax.lax.cond(cond, body, lambda c: c, carry)


def _walk_tile(plan, kernel, causal, window, j, n_minor, whole, piece,
               carry=0):
    """Walk the minor tiles of major tile ``j`` (its index in the
    sequence: a number or a traced value) in the order of their index,
    threading ``carry``: ``whole(start, carry)`` for each tile of the run,
    ``piece(p, start, carry)`` for each piece of an edge tile that lies
    inside the sequence; ``start`` is the first minor position."""
    (t_lo, t_hi), edges = plan.walk(kernel, causal, window)
    minor = plan.block_q if kernel == DKV else plan.block_k

    def edge(t, pieces, carry):
        def body(carry):
            for p in pieces:
                start = j * plan.block_q + p.minor
                carry = piece(p, start if _static(start)
                              else pl.multiple_of(start, p.n), carry)
            return carry
        at = j + t
        inside = (0 <= at < n_minor) if _static(at) else (
            at >= 0 if t < 0 else at < n_minor if t > 0 else True)
        return _when(inside, body, carry)

    lo = 0 if t_lo is None else _at_least(j + t_lo, 0)
    hi = n_minor if t_hi is None else _at_most(j + t_hi, n_minor)
    before = [e for e in edges if t_lo is not None and e[0] < t_lo]
    for t, pieces in before:
        carry = edge(t, pieces, carry)
    carry = _loop(lo, hi, lambda i, c: whole(
        i * minor if _static(i) else pl.multiple_of(i * minor, minor), c),
        carry)
    for t, pieces in edges[len(before):]:
        carry = edge(t, pieces, carry)
    return carry


def _major_tile(tiles, i, n_steps):
    """Index in the sequence of the ``i``-th of the ``tiles`` major tiles
    of this grid step: a number where the step holds the whole sequence."""
    return i if n_steps == 1 else pl.program_id(1) * tiles + i


def _piece_mask(p, lo, hi, minor_axis):
    if not (p.cut_lo or p.cut_hi):
        return None
    return lambda s: _band_mask(s, p.minor - p.major, p, lo, hi, minor_axis)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                window, plan, seq_k, n_steps):
    bq, bk, d = plan.block_q, plan.block_k, q_ref.shape[2]
    lo, hi = (-(window - 1) if window else None), 0

    for i in range(plan.tiles):
        j = _major_tile(plan.tiles, i, n_steps)
        rows = slice(i * bq, (i + 1) * bq)
        q = q_ref[0, rows, :].astype(jnp.float32) * scale

        # the online softmax's state (m, l, acc) is carried as values
        def update(state, start, mask=None):
            m_prev, l_prev, acc = state
            k = k_ref[0, pl.ds(start, bk), :].astype(jnp.float32)
            v = v_ref[0, pl.ds(start, bk), :].astype(jnp.float32)
            s = _qk(q, k)
            if mask is not None:
                s = mask(s)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # the first keys a window reaches can lie wholly behind the
            # window of a tile's last rows: their maximum is still -inf
            m_ref = jnp.where(m_new == NEG_INF, 0.0, m_new) \
                if window else m_new
            alpha = jnp.exp(m_prev - m_ref)
            p = jnp.exp(s - m_ref)
            return (m_new,
                    alpha * l_prev + jnp.sum(p, axis=1, keepdims=True),
                    acc * alpha + jnp.dot(
                        p, v, preferred_element_type=jnp.float32))

        m, l, acc = _walk_tile(
            plan, FWD, causal, window, j, seq_k // bk,
            lambda start, state: update(state, start),
            lambda p, start, state: update(state, start,
                                           _piece_mask(p, lo, hi, 1)),
            (jnp.full((bq, 1), NEG_INF, jnp.float32),
             jnp.zeros((bq, 1), jnp.float32),
             jnp.zeros((bq, d), jnp.float32)))
        o_ref[0, rows, :] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, 0, rows] = (m + jnp.log(l))[:, 0]


def fwd_kv_bytes(seq_k: int, d: int, dtype) -> int:
    """VMEM the forward's whole K and V of one head take: two arrays,
    double-buffered."""
    return 2 * 2 * seq_k * d * jnp.dtype(dtype).itemsize


def fwd_vmem_bytes(seq_k: int, d: int, dtype, block_q: int,
                   block_k: int, tiles: int = 1) -> int:
    """VMEM a forward grid step takes, about: one head's K and V, the q and
    o blocks of the ``tiles`` query tiles it holds (double-buffered), and
    the float32 tiles of the one being computed (q and the accumulator, a K
    and a V block, the logits and the probabilities)."""
    item = jnp.dtype(dtype).itemsize
    return fwd_kv_bytes(seq_k, d, dtype) \
        + tiles * 2 * 2 * block_q * d * item \
        + 4 * (2 * block_q * d + 2 * block_k * d + 2 * block_q * block_k)


def _vmem_params(need):
    """Nothing where a step's ``need`` fits the compiler's default scoped
    limit; past it, a limit of the call's own."""
    if need <= _VMEM_DEFAULT:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=need + (16 << 20))}


def bwd_dq_vmem_bytes(seq_k: int, d: int, dtype, block_q: int,
                      block_k: int, tiles: int = 1) -> int:
    """VMEM a dq grid step takes at the chip's matmul precision, about: one
    head's K and V, the q, do and dq blocks of the ``tiles`` query tiles it
    holds (double-buffered) with their float32 accumulators, and the
    float32 tiles of the one being computed (q and do, a K and a V block,
    the logits, the probabilities and their gradient)."""
    item = jnp.dtype(dtype).itemsize
    return fwd_kv_bytes(seq_k, d, dtype) \
        + tiles * block_q * d * (3 * 2 * item + 4) \
        + 4 * (2 * block_q * d + 2 * block_k * d + 3 * block_q * block_k)


def bwd_dkv_vmem_bytes(seq_q: int, d: int, dtype, block_q: int,
                       block_k: int, tiles: int = 1) -> int:
    """VMEM a dk/dv grid step takes, about: one head's whole q and do, lse
    and delta (a row of float32 takes eight sublanes), the k, v, dk and dv
    blocks of the ``tiles`` key tiles it holds (all double-buffered), their
    two float32 accumulators, and the float32 tiles of the one being
    computed (k and v, a q and a do block, the logits, the probabilities
    and their gradient)."""
    item = jnp.dtype(dtype).itemsize
    return 2 * 2 * seq_q * d * item + 2 * 2 * seq_q * 8 * 4 \
        + tiles * block_k * d * (4 * 2 * item + 2 * 4) \
        + 4 * (2 * block_k * d + 2 * block_q * d + 3 * block_q * block_k)


def _whole_tiles(plan, block_q, block_k):
    """``plan``, or with none the one whose dq kernel too masks every edge
    tile whole, a tile a grid step (what the kernels did before they had a
    plan)."""
    return plan or BlockPlan(block_q, block_k, max(block_q, block_k))


def _kv_map(group):
    """Index map of a whole-sequence k/v operand whose head serves
    ``group`` query heads."""
    if group == 1:
        return lambda i, j: (i, 0, 0)
    return lambda i, j: (i // group, 0, 0)


def _name(stem, tag):
    return f"{stem}_{tag}" if tag else stem


def _own(i, j):
    return (i, j, 0)


def _traced_once(kernel):
    """``kernel`` under ``jax.jit`` with everything but its arrays static.
    A program of many layers calls each kernel with one shape: under the
    outer trace JAX then traces this function, the kernel's body with it,
    ONCE, and lowers it to one private function that every layer calls, so
    the kernel's Mosaic payload stands once in the module and not once a
    layer. XLA inlines the calls: the compiled program is what it was, a
    custom call a layer under the kernel's name. ``interpret`` is one of
    the static arguments, read at the call and not at the first trace."""
    jitted = jax.jit(kernel, static_argnames=(
        "causal", "scale", "block_q", "block_k", "window", "tag", "plan",
        "interpret"))

    @functools.wraps(kernel)
    def call(*args, **kw):
        return jitted(*args, **kw, interpret=_interpret())
    return call


@_traced_once
def _flash_fwd(q, k, v, causal, scale, block_q, block_k, window=0, tag="",
               plan=None, interpret=False):
    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    kv = _kv_map(bh // k.shape[0])
    plan = _whole_tiles(plan, block_q, block_k)
    tiles = plan.tiles
    rows = tiles * plan.block_q
    grid = (bh, seq_q // rows)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window, plan=plan,
        seq_k=seq_k, n_steps=grid[1])
    o, lse = pl.pallas_call(
        kernel,
        name=_name("flash_fwd", tag),
        grid=grid,
        **_vmem_params(fwd_vmem_bytes(seq_k, d, k.dtype, plan.block_q,
                                      plan.block_k, tiles)),
        in_specs=[
            pl.BlockSpec((1, rows, d), _own),
            pl.BlockSpec((1, seq_k, d), kv),
            pl.BlockSpec((1, seq_k, d), kv),
        ],
        out_specs=[
            pl.BlockSpec((1, rows, d), _own),
            # lse rides as [bh, 1, seq]: Mosaic requires the last two
            # block dims to be (div 8, div 128) or full — (1, block_q)
            # on a 2-D array satisfies neither, (1, 1, block_q) does.
            pl.BlockSpec((1, 1, rows), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq_q), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse


# --------------------------------------------------------------- backward

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale, causal, window, plan, seq_k, n_steps):
    bq, bk = plan.block_q, plan.block_k
    lo, hi = (-(window - 1) if window else None), 0
    tiles = plan.dq_tiles

    for i in range(tiles):
        j = _major_tile(tiles, i, n_steps)
        tile = slice(i * bq, (i + 1) * bq)
        q = q_ref[0, tile, :].astype(jnp.float32) * scale
        do = do_ref[0, tile, :].astype(jnp.float32)
        lse = lse_ref[0, 0, tile][:, None]
        delta = delta_ref[0, 0, tile][:, None]
        dq_scr[i] = jnp.zeros(dq_scr.shape[1:], jnp.float32)

        def update(rows, n_rows, start, n_keys, mask=None):
            at = (i, pl.ds(rows, n_rows))
            part = slice(rows, rows + n_rows)
            k = k_ref[0, pl.ds(start, n_keys), :].astype(jnp.float32)
            v = v_ref[0, pl.ds(start, n_keys), :].astype(jnp.float32)
            s = _qk(q[part], k)
            if mask is not None:
                s = mask(s)
            p = jnp.exp(s - lse[part])
            ds = p * (_qk(do[part], v) - delta[part])
            dq_scr[at] = dq_scr[at] + jnp.dot(
                ds, k, preferred_element_type=jnp.float32)

        _walk_tile(
            plan, DQ, causal, window, j, seq_k // bk,
            lambda start, c: update(0, bq, start, bk) or c,
            lambda p, start, c: update(p.major, p.n, start, p.n,
                                       _piece_mask(p, lo, hi, 1)) or c)
        dq_ref[0, tile, :] = (dq_scr[i] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                    window, plan, seq_q, n_steps):
    bq, bk = plan.block_q, plan.block_k
    lo, hi = 0, (window - 1 if window else None)

    for i in range(plan.tiles):
        j = _major_tile(plan.tiles, i, n_steps)
        tile = slice(i * bk, (i + 1) * bk)
        k = k_ref[0, tile, :].astype(jnp.float32)
        v = v_ref[0, tile, :].astype(jnp.float32)
        dk_scr[i] = jnp.zeros(dk_scr.shape[1:], jnp.float32)
        dv_scr[i] = jnp.zeros(dv_scr.shape[1:], jnp.float32)

        def update(start, mask=None):
            rows = pl.ds(start, bq)
            q = q_ref[0, rows, :].astype(jnp.float32) * scale
            do = do_ref[0, rows, :].astype(jnp.float32)
            lse = lse_ref[0, 0, rows][:, None]
            delta = delta_ref[0, 0, rows][:, None]
            s = _qk(q, k)
            if mask is not None:
                s = mask(s)
            p = jnp.exp(s - lse)
            dv_scr[i] = dv_scr[i] + _tn(p, do)
            ds = p * (_qk(do, v) - delta)
            dk_scr[i] = dk_scr[i] + _tn(ds, q)

        _walk_tile(
            plan, DKV, causal, window, j, seq_q // bq,
            lambda start, c: update(start) or c,
            lambda p, start, c: update(start,
                                       _piece_mask(p, lo, hi, 0)) or c)
        # q was pre-scaled, so dk already carries the scale factor
        dk_ref[0, tile, :] = dk_scr[i].astype(dk_ref.dtype)
        dv_ref[0, tile, :] = dv_scr[i].astype(dv_ref.dtype)


@_traced_once
def _flash_bwd_dq(q, k, v, do, lse, delta, causal, scale, block_q, block_k,
                  window=0, tag="", plan=None, interpret=False):
    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    kv = _kv_map(bh // k.shape[0])
    plan = _whole_tiles(plan, block_q, block_k)
    tiles = plan.dq_tiles
    rows = tiles * plan.block_q
    row = lambda i, j: (i, 0, j)                              # noqa: E731
    grid = (bh, seq_q // rows)
    return pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal, window=window,
            plan=plan, seq_k=seq_k, n_steps=grid[1]),
        name=_name("flash_bwd_dq", tag),
        grid=grid,
        **_vmem_params(bwd_dq_vmem_bytes(seq_k, d, k.dtype, plan.block_q,
                                         plan.block_k, tiles)),
        in_specs=[
            pl.BlockSpec((1, rows, d), _own),
            pl.BlockSpec((1, seq_k, d), kv),
            pl.BlockSpec((1, seq_k, d), kv),
            pl.BlockSpec((1, rows, d), _own),
            pl.BlockSpec((1, 1, rows), row),
            pl.BlockSpec((1, 1, rows), row),
        ],
        out_specs=pl.BlockSpec((1, rows, d), _own),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tiles, plan.block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)


@_traced_once
def _flash_bwd_dkv(q, k, v, do, lse, delta, causal, scale, block_q, block_k,
                   window=0, tag="", plan=None, interpret=False):
    """One (dk, dv) partial per QUERY head: a KV head's group is summed by
    the caller."""
    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    group = bh // k.shape[0]
    plan = _whole_tiles(plan, block_q, block_k)
    tiles = plan.tiles
    keys = tiles * plan.block_k
    whole = lambda i, j: (i, 0, 0)                            # noqa: E731
    kv_block = _own if group == 1 else (lambda i, j: (i // group, j, 0))
    grid = (bh, seq_k // keys)
    acc = pltpu.VMEM((tiles, plan.block_k, d), jnp.float32)
    return pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal, window=window,
            plan=plan, seq_q=seq_q, n_steps=grid[1]),
        name=_name("flash_bwd_dkv", tag),
        grid=grid,
        **_vmem_params(bwd_dkv_vmem_bytes(seq_q, d, q.dtype, plan.block_q,
                                          plan.block_k, tiles)),
        in_specs=[
            pl.BlockSpec((1, seq_q, d), whole),
            pl.BlockSpec((1, keys, d), kv_block),
            pl.BlockSpec((1, keys, d), kv_block),
            pl.BlockSpec((1, seq_q, d), whole),
            pl.BlockSpec((1, 1, seq_q), whole),
            pl.BlockSpec((1, 1, seq_q), whole),
        ],
        out_specs=[
            pl.BlockSpec((1, keys, d), _own),
            pl.BlockSpec((1, keys, d), _own),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_k, d), k.dtype),
            jax.ShapeDtypeStruct((bh, seq_k, d), v.dtype),
        ],
        scratch_shapes=[acc, acc],
        interpret=interpret,
    )(q, k, v, do, lse, delta)


def _flash_bwd(causal, scale, block_q, block_k, res, g, window=0, tag="",
               plan=None):
    q, k, v, o, lse = res
    bh_kv, seq_k, d = k.shape
    group = q.shape[0] // bh_kv
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]
    args = (q, k, v, g, lse, delta, causal, scale, block_q, block_k, window,
            tag, plan)
    dq = _flash_bwd_dq(*args)
    dk, dv = _flash_bwd_dkv(*args)
    if group > 1:
        dk, dv = (a.reshape(bh_kv, group, seq_k, d).astype(jnp.float32)
                  .sum(axis=1).astype(a.dtype) for a in (dk, dv))
    return dq, dk, dv


# The [b, h, s, d] level is where a program that spans several chips
# meets the kernels: each (batch, head) pair is independent, so under
# utils.kernel_sharding these two wrappers run the 3-D kernels above on
# each chip's own shard of batch and heads (see utils.shard_parallel).

def _plan_4d(q, k, causal, block_q, block_k, window, plan):
    return plan or block_plan(q.shape[2], k.shape[2], q.shape[3], causal,
                              window, block_q, block_k, k.dtype)


def _fwd_4d(q, k, v, causal, scale, block_q, block_k, window, tag, plan):
    b, h, seq_q, d = q.shape
    plan = _plan_4d(q, k, causal, block_q, block_k, window, plan)
    o, lse = _flash_fwd(*(a.reshape(-1, a.shape[2], d)
                          for a in (q, k, v)),
                        causal, scale, block_q, block_k, window, tag, plan)
    return o.reshape(q.shape), lse.reshape(b, h, 1, seq_q)


def _bwd_4d(q, k, v, o, lse, do, causal, scale, block_q, block_k, window,
            tag, plan):
    b, h, seq_q, d = q.shape
    plan = _plan_4d(q, k, causal, block_q, block_k, window, plan)
    res = tuple(a.reshape(-1, a.shape[2], d) for a in (q, k, v, o)) \
        + (lse.reshape(b * h, 1, seq_q),)
    dq, dk, dv = _flash_bwd(causal, scale, block_q, block_k, res,
                            do.reshape(b * h, seq_q, d), window, tag, plan)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


_fwd_call = shard_parallel(_fwd_4d, ("bh--",) * 3, ("bh--", "bh--"))
_bwd_call = shard_parallel(_bwd_4d, ("bh--",) * 6, ("bh--",) * 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, scale, block_q, block_k, window, tag, plan):
    return _fwd_call(q, k, v, causal, scale, block_q, block_k, window,
                     tag, plan)[0]


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k, window, tag,
                   plan):
    # JAX's jvp wraps the first scope it meets ("jvp(flash_fwd)") and a
    # compiled program names a kernel by its last: one scope around the
    # call takes the wrapper, and the kernel keeps the primal's name
    with jax.named_scope("flash_attention"):
        o, lse = _fwd_call(q, k, v, causal, scale, block_q, block_k, window,
                           tag, plan)
    o, lse = map(checkpoint_name, (o, lse), FLASH_RESIDUAL_NAMES)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, window, tag, plan, res,
                   g):
    return _bwd_call(*res, g, causal, scale, block_q, block_k, window, tag,
                     plan)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=512, block_k=512, window=0, tag="",
                    plan: Optional[BlockPlan] = None):
    """Flash attention on [b, h, s, d] (or [bh, s, d]) inputs.

    Returns attention output with the input's shape/dtype. Raises
    ValueError for shapes the kernel cannot tile (caller decides the
    fallback); self-attention (seq_q == seq_k) plus cross shapes whose
    sequences are divisible by a power-of-two block are supported.

    ``k`` / ``v`` may have fewer heads than ``q`` (a divisor of its count):
    query head ``j`` reads KV head ``j // (h_q // h_kv)``. ``window`` > 0
    (causal only) limits query ``i`` to keys ``i-window+1 .. i``. ``tag``
    is appended to the three kernels' names. ``block_q`` / ``block_k`` are
    the largest tile :func:`block_plan` may take; ``plan`` puts a
    :class:`BlockPlan` in its place (tests and ``tools/
    flash_block_plans.py`` time and check each form that way; no caller
    of the program passes one).

    Sequence-length limit: every grid step keeps the WHOLE K and V of
    one head in VMEM (the ``(1, seq_k, d)`` blocks above; q, do, lse and
    delta likewise in the dk/dv kernel), double-buffered: ``8 s d`` bytes
    in bfloat16 (:func:`fwd_kv_bytes`), beside the q / o blocks and the
    loop's float32 tiles (:func:`fwd_vmem_bytes`: 3.5 MiB at d=128, 5 MiB
    at d=256 with blocks of 512, and 0.5 or 1 MiB more for each further
    query tile a step holds). Under the compiler's default scoped limit
    (16 MiB) the v5e compiler accepts, forward-only, d=128 through s=12288
    and d=256 through s=4096, and refuses d=128 at s=16384 and d=256 at
    s=6144 and s=8192 ("RESOURCE_EXHAUSTED: Ran out of memory in memory
    space vmem", at compile time under jit: described-chip compiles, PR
    58). A forward past the default asks for a scoped limit of its own
    (what it needs plus 16 MiB) and is then accepted at d=256 through
    s=32768 and at d=128 through s=65536 (``tests/test_chip_compile.py``
    holds the served shapes); a forward that fits the default is compiled
    exactly as before. Past :data:`_FWD_KV_MOST` (64 MiB of K and V) the
    call is refused HERE, a ``ValueError`` before anything is built, which
    ``fused_attention_qkv`` catches and answers with the composed form.
    The two backward kernels do the same from :func:`bwd_dq_vmem_bytes` and
    :func:`bwd_dkv_vmem_bytes` (the dk/dv kernel holds a head's whole q and
    do), and at the training cells' shapes neither passes the default: the
    plan gives a dq step only as many tiles as leave it a MiB under it (at
    s=8192, d=128 two, 14 MiB; four stood AT 16.0 MiB by the estimate and
    were refused by 0.4 MiB at ``highest`` precision, whose float32
    products are six passes wide). A limit of its own is no way round
    that: a call that asks for more scoped VMEM takes it from the
    operations around it, and with 32 MiB for this one kernel
    ``laguna_pretrain_8k``'s step read 0.4% slower in two pairs of runs (PR
    59). Longer sequences still want K and V streamed by block (ROADMAP S5
    / R8a).

    Inside a program that spans several chips (utils.kernel_sharding)
    the kernels run on each chip's own (batch, head) shard; sequence and
    head_dim are whole per chip.
    """
    squeeze = q.ndim == 3
    if squeeze:
        q, k, v = q[:, None], k[:, None], v[:, None]
    b, h, seq_q, d = q.shape
    seq_k = k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    bq = pick_block(seq_q, block_q, minimum=16)
    bk = pick_block(seq_k, block_k, minimum=16)
    if not bq or not bk:
        raise ValueError(
            f"flash_attention: cannot tile seq_q={seq_q}, seq_k={seq_k}")
    if causal and seq_q != seq_k:
        raise ValueError("causal flash_attention requires seq_q == seq_k")
    if window and not causal:
        raise ValueError("flash_attention: a window needs causal=True")
    if h % k.shape[1] or k.shape[1] != v.shape[1]:
        raise ValueError(
            f"flash_attention: {h} query heads over {k.shape[1]} KV heads")
    resident = fwd_kv_bytes(seq_k, pad_lane_dim(d), k.dtype)
    if resident > _FWD_KV_MOST:
        raise ValueError(
            f"flash_attention: one head's K and V at seq_k={seq_k}, d={d} "
            f"({k.dtype}) take {resident >> 20} MiB of VMEM double-buffered, "
            f"over the {_FWD_KV_MOST >> 20} MiB the forward may ask for: K "
            f"and V are not streamed by block yet")
    # head_dim rides the lane axis whole; an unaligned width is padded
    # with zero columns (k's zero columns contribute nothing to the
    # logits, v's produce zero output columns sliced off below) rather
    # than rejected — pick_block's divisor rule never applies to d.
    dp = pad_lane_dim(d)
    if dp != d:
        pad = [(0, 0), (0, 0), (0, 0), (0, dp - d)]
        q, k, v = (jnp.pad(a, pad) for a in (q, k, v))
    out = _flash(q, k, v, causal, float(scale), bq, bk,
                 _effective_window(window, seq_k), str(tag), plan)
    if dp != d:
        out = out[..., :d]
    return out[:, 0] if squeeze else out
