"""The mixing of a model's n residual streams, counted from a
configuration file: what a file with `hc_mult` must move and do for the
hyper-connections alone, and how a device trace tells their operations.

It counts the work of the MODEL, not of an implementation. Per sublayer
(two a layer: attention, feed-forward) and decode step,

* every live stream's n x `hidden_size` residual values READ once and
  WRITTEN once, in the dtype the streams are carried in (bf16: 2 x
  n x C x 2 B a row; 57,344 B at n 4 and C 3,584);
* the sublayer's mixing projection phi [n C, n + n + n^2] once, in bf16
  whatever the weights are (the program keeps it float, as a router);
* the projection (2 n C (2 n + n^2) operations a row), the read
  (2 n C), the write (2 n (n + 1) C) and `hc_sinkhorn_iters` rounds over
  an n x n matrix (4 n^2 a round): under an operation a byte, so the
  bytes bind on any chip.

Whatever serves the mixing moves at least that: an implementation that
wrote the streams in float32, or read them once for the coefficients
and again for the write, moves more and the share says so. A mixed
block's chunk columns are mixed too; the least time counts the decode
rows alone, as `latent_attn_roofline` does, so the share reads low by
the chunk columns' part (a fifth at 128 rows and one chunk of 32).

`servebench/peaks.py` does not count the mixing (its keys are older than
`hc_mult`): `block_roofline` reads about 2 % low in a cell of this
family until a `benchmark` PR adds `hc_bytes` to `step_parts`.

stdlib only.
"""
from __future__ import annotations

import re
from typing import Dict

#: the streams and phi are held in bfloat16
BYTES = 2.0


def has_streams(config: Dict) -> bool:
    return bool(config.get("hc_mult"))


def mix_width(config: Dict) -> int:
    """Columns of a sublayer's mixing projection: n + n + n^2."""
    n = config["hc_mult"]
    return n * (2 + n)


def sublayers(config: Dict) -> int:
    """Sublayers that mix, over the layers AS RUN."""
    return 2 * config["num_hidden_layers"]


def row_bytes(config: Dict) -> float:
    """Bytes one row's streams cost one sublayer: read and written."""
    return 2 * config["hc_mult"] * config["hidden_size"] * BYTES


def phi_bytes(config: Dict) -> float:
    return config["hc_mult"] * config["hidden_size"] * mix_width(config) \
        * BYTES


def row_flops(config: Dict) -> float:
    """Operations one row costs one sublayer."""
    n, C = config["hc_mult"], config["hidden_size"]
    return 2.0 * n * C * mix_width(config) + 2.0 * n * C \
        + 2.0 * n * (n + 1) * C + 4.0 * n * n * config["hc_sinkhorn_iters"]


def hc_least_seconds(config: Dict, device_kind: str, chips: int, steps: int,
                     rows: float) -> Dict[str, float]:
    """The least time `chips` chips could take for the mixing of one
    block of `steps` decode steps with `rows` live streams. Returns the
    bytes, the operations, both bounds, which one binds, and one step's
    bytes by part (`parts`: `streams`, `phi`)."""
    from servebench import peaks
    S = sublayers(config)
    parts = {"streams": S * rows * row_bytes(config),
             "phi": S * phi_bytes(config)}
    return dict(peaks.least_seconds(
        steps * (parts["streams"] + parts["phi"]),
        steps * S * rows * row_flops(config), device_kind, chips),
        parts=parts, rows=rows)


# -- the mixing's operations in a device trace --------------------------------
#
# The mixing is XLA's own operations (no kernel is asked of it), and a
# trace names an operation by its HLO text: the instruction's name and
# the shape of its result (servebench/xplane.py keeps the first 64
# characters, every character outside [A-Za-z0-9_.:-] as `_`, and none
# of an operation's metadata: the program's scope `hc_mix` is in the
# profile and not in the summary a reader gets). So the operations are
# told by the three shapes that ONLY the mixing produces, from the
# file's sizes n = `hc_mult`, C = `hidden_size`, K = n (2 + n), with R
# the rows of a step (any number):
#
#   [n, R, 1, C]    the streams: the write's result (or one stream of it,
#       [1, R, 1, C]), their float32 copy, the embedding's fan-out
#   [n, C, K], [n C, K]   phi, cut out by layer and as the product reads it
#   [K, R], [n^2, R]      the projection's result and its res~ part
#
# What is NOT told, because its result is a vector of rows [R] or [n, R]
# or of K values, shapes a counter or a router's score has as well: the
# norm's sum of squares, the rsqrt, b and the alphas, H_pre and H_post,
# the Sinkhorn's rounds (XLA fuses several at a time, tuples of float32
# [R]), and the read's result h [R, C], which is every activation's
# shape and fused with the sublayer's pre-norm. In the traced runs of
# PR 49 the three shapes hold 81 % of what a pattern with those vectors
# held (PERF.md section 5), so `hc_share` reads about a fifth low and
# `hc_roofline` as much high; a `benchmark` PR that carries an
# operation's scope through xplane.py reads `hc_mix` and drops this.
#
# tests/servebench/test_servebench_hc.py holds the pattern to the names of
# a traced run of the cell: must-match, must-NOT-match, and the mixing's
# own operations it leaves out.

def hc_patterns(config: Dict):
    """A compiled pattern over a trace's cleaned operation names."""
    n, C = config["hc_mult"], config["hidden_size"]
    K = mix_width(config)
    return re.compile(
        # not the layer scan itself, whose carry holds the streams (its
        # self time is the loop's)
        r"^(?!_while[._]).*?(?:"
        rf"_(?:1|{n})_[0-9]+_1_{C}__"              # [n, R, 1, C]; one stream
        rf"|_{n}_{C}_{K}__|_{n * C}_{K}__"         # phi
        rf"|_f32_(?:{K}|{n * n})_[0-9]+__)")       # [K, R], [n^2, R]


def hc_op_seconds(ctx):
    """Self seconds of the mixing's operations in the trace, or None
    where there is no trace or the configuration has one stream."""
    ops = (ctx.trace or {}).get("ops")
    if not ops or not has_streams(ctx.config):
        return None
    pat = hc_patterns(ctx.config)
    return sum(sec for name, sec, _ in ops if pat.search(name))
