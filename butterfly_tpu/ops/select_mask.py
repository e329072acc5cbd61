"""Pallas selection: the k positions of a row that score highest, as a
mask, found by COUNTING (cache/paged.py _selection has the rule for
when).

models/common.py select_mask asks lax.top_k for ONE number, the k-th
highest score, and counts the ties at it with a running sum. On the chip
that is a full bitonic sort of every row (150 us at 32 rows of 7,168,
k = 2,048) and a `reduce-window` behind it (51 us), where the rows are
917 KB, 1.1 us of the memory's rate (PERF.md, PR 55). Nothing needs the
order. Here a block of rows stays in fast memory and is only ever
COUNTED:

* a float32 score becomes an int32 key of the same order: the bits b of
  the score (-0.0 as +0.0: they compare equal), b ^ 0x7fffffff where
  the sign is set. Its UNSIGNED twin, key ^ 0x80000000, is what the
  descent below builds digit by digit; a position the row may not attend
  gets the least key there is (unsigned 0), under -inf's;
* the k-th highest key by descent over its bits, KEY_BITS a pass: with
  `prefix` the digits found so far, a pass counts for each next digit d
  the keys >= prefix | d << shift, and takes the largest d whose count
  is still >= k. Exactly lax.top_k(s, k)[0][..., -1]; fewer than k valid
  positions give the least key, and every valid one scores above it;
* the tie the same way: room = k - count(key > k-th), and of the
  positions whose key EQUALS the k-th the `room` lowest are taken: the
  largest P with count(equal & position < P) <= room, by the same
  descent over the bits of a position. No prefix sum over the row.

A count is a compare and an add a register over the row block, LANES
at a time into accumulators one register deep, and ONE cross-lane sum a
candidate: the passes are a chain (a pass needs the digit before it), so
a block is all the rows there are up to ROWS, whose row groups fill the
chain's latency. The result is [R, n] int32, what the two selecting
reads take their selection in (ops/sparse_attention.py,
ops/latent_attention.py latent_select_attention), one block a grid step
in XLA's dense tiles. (NEVER [R, 1, n]: a unit dim before the lanes is
a row a tile, T(1,128), and XLA's consumers inherit it. PERF.md, PR 53.)

On the CPU backend the wrapper runs the kernel in interpreter mode;
everywhere else it is compiled (ops/__init__.py has the rule).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from butterfly_tpu.ops import note_kernel, resolve_interpret
from butterfly_tpu.ops.flash_attention import live_auto_mesh

#: rows one block holds in fast memory (a grid step): [32, 7168] int32 is
#: 917 KB in, as much out. Four row groups of 8 share one chain of passes
ROWS = 32
#: and the bytes one block may take (it is held twice over, coming and
#: going, each double-buffered)
BLOCK_BYTES = 2 << 20
#: bits of the key, and of a position, one pass settles: 2 ** BITS - 1
#: candidates counted side by side. One bit a pass is 45 links of the
#: chain, four bits 15 candidates' compares a register: on the chip at
#: the cells' geometry 1, 2 and 4 read 28.1, 28.0 and 31.2 us a call in
#: one run and within a microsecond in another (PERF.md, PR 55)
KEY_BITS = 2        # a divisor of the key's 32 bits
POS_BITS = 2
#: lanes one step of a count loads: two registers a row group, so that
#: three candidates' accumulators and the keys stay in registers (128,
#: 256 and 512 read the same to a microsecond on the chip)
LANES = 256

_SIGN = -2 ** 31        # int32's 0x80000000: key <-> its unsigned twin


def _block_rows(R: int, n: int) -> int:
    """The rows of one block of [R, n] 32-bit values, 0 if none will do:
    whole sublane tiles, as many as divide R up to ROWS, or every row
    where they are no whole tiles; a block no larger than BLOCK_BYTES."""
    for rows in (ROWS, 16, 8) if R % 8 == 0 else (R,):
        if R % rows == 0 and rows * n * 4 <= BLOCK_BYTES:
            return rows
    return 0


def fits(scores: jax.Array, k: int) -> bool:
    """Can the kernel select k of these scores [R, n]? There must be
    something to leave out (n > k, else the selection is `valid`), and
    never under a mesh that GSPMD still partitions (a bare Mosaic call
    is opaque to it). Compiled, a row is whole lanes and a block of rows
    fits fast memory; interpreted (the CPU backend) any rows will do.
    Anything else keeps lax.top_k."""
    R, n = scores.shape
    if n <= k or live_auto_mesh() or not _block_rows(R, n):
        return False
    return resolve_interpret(None) or n % 128 == 0


def sort_keys(scores: jax.Array, valid: jax.Array) -> jax.Array:
    """int32 keys in the scores' order (float32; equal scores, +-0.0
    among them, equal keys), the least int32 where not valid."""
    b = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    b = jnp.where(b == _SIGN, 0, b)                        # -0.0 is 0.0
    key = b ^ (jnp.right_shift(b, 31) & jnp.int32(0x7FFFFFFF))
    return jnp.where(valid, key, _SIGN)


def _counts(key_ref, hit, cands):
    """[rows, 1] int32 for each of `cands` ([rows, 1] int32 each): how
    many positions of the block hit(keys, positions, cand) holds at.
    One walk over the block, LANES at a time."""
    rows, n = key_ref.shape
    acc, rest = [None] * len(cands), [0] * len(cands)
    for at in range(0, n, LANES):
        keys = key_ref[:, at:at + LANES]
        pos = at + jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
        for i, cand in enumerate(cands):
            one = hit(keys, pos, cand).astype(jnp.int32)
            if keys.shape[1] < LANES:       # a last, narrower step
                rest[i] = jnp.sum(one, axis=1, keepdims=True)
            else:
                acc[i] = one if acc[i] is None else acc[i] + one
    return [r if a is None else jnp.sum(a, axis=1, keepdims=True) + r
            for a, r in zip(acc, rest)]


def _descend(key_ref, hit, keep, width: int, bits: int):
    """The largest value of `width` bits, as [rows, 1] int32, whose
    count (_counts of `hit` at the value) satisfies `keep`, a predicate
    that holds up to some value and for none above it; 0 if for none.
    `bits` bits a pass, the highest first."""
    rows = key_ref.shape[0]
    passes = -(-width // bits)

    def one_pass(i, prefix):
        shift = (passes - 1 - i) * bits
        cands = [prefix | jnp.left_shift(jnp.int32(d), shift)
                 for d in range(1, 2 ** bits)]
        digit = sum(keep(c).astype(jnp.int32)
                    for c in _counts(key_ref, hit, cands))
        return prefix | jnp.left_shift(digit, shift)

    return jax.lax.fori_loop(0, passes, one_pass,
                             jnp.zeros((rows, 1), jnp.int32))


def _select_kernel(key_ref, o_ref, *, k: int):
    """One grid step is one block of rows: key_ref [rows, n] int32
    (sort_keys), o_ref [rows, n] int32, 1 at the k positions whose keys
    are highest, equal keys by position, the lower first."""
    n = key_ref.shape[1]
    # the k-th highest key, as its unsigned twin: keys >= a candidate u
    # are those whose signed form is >= u ^ sign
    kth = _descend(key_ref, lambda keys, _, u: keys >= (u ^ _SIGN),
                   lambda c: c >= k, 32, KEY_BITS) ^ _SIGN
    above, = _counts(key_ref, lambda keys, _, kth: keys > kth, [kth])
    room = k - above
    # of the positions AT the k-th key, those under `cut` are `room`
    # or fewer: the lower positions first, as lax.top_k takes them
    cut = _descend(key_ref,
                   lambda keys, pos, p: (keys == kth) & (pos < p),
                   lambda c: c <= room, n.bit_length(), POS_BITS)
    for at in range(0, n, LANES):
        keys = key_ref[:, at:at + LANES]
        pos = at + jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
        sel = ((keys > kth) | ((keys == kth) & (pos < cut))) \
            & (keys != _SIGN)
        o_ref[:, at:at + LANES] = sel.astype(jnp.int32)


def _call(keys: jax.Array, k: int, interpret: bool) -> jax.Array:
    """The selection [R, n] int32 of keys [R, n] int32 (sort_keys), a
    block of rows a grid step."""
    R, n = keys.shape
    rows = _block_rows(R, n)
    block = pl.BlockSpec((rows, n), lambda r: (r, 0))
    return pl.pallas_call(
        functools.partial(_select_kernel, k=k),
        grid=(R // rows,), in_specs=[block], out_specs=block,
        out_shape=jax.ShapeDtypeStruct((R, n), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(keys)


# The jitted function's name is the Mosaic call's name in a device
# trace (`select_mask.N = s32[R, S_max]`), and S_max in its result is
# what the benchmark's readers of the selecting path count it by.
@jax.named_scope("attn_select")
@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def select_mask(scores: jax.Array, valid: jax.Array, k: int, *,
                interpret: bool | None = None) -> jax.Array:
    """models.common.select_mask as [R, n] int32 (1: selected): of the
    positions of each row of scores [R, n] float32 that are `valid`
    [R, n] bool, the k that score highest, equal scores by position, the
    lower first; every valid position where there are no more than k."""
    interpret = resolve_interpret(interpret)
    note_kernel("select_mask", interpret)
    return _call(sort_keys(scores, valid), k, interpret)
