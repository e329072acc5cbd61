"""A Gated DeltaNet mixer, counted from a configuration file: what a model
with linear-attention layers (`layer_types` "linear_attention" and the
`linear_*` keys) must move to advance every live stream by one position in
one such layer, and how a device trace tells the mixers' operations.

It counts the work of the MODEL (Yang, Kautz, Hatamizadeh, "Gated Delta
Networks", ICLR 2025), not of an implementation: per linear-attention
layer and decode step,

* the mixer's five wide projections, once: hidden x (2 x H dk + 2 x H dv)
  in (q, k, v and the output gate z) and H dv x hidden out, H =
  `linear_num_value_heads`, dk = `linear_key_head_dim`, dv =
  `linear_value_head_dim` (`proj_params`: 6 x hidden^2 at the published
  ratios), a byte a parameter where the configuration serves int8 codes,
  else two; and the two head-wide projections a and b, hidden x 2 H, at
  two bytes whatever the others are (`ab_params`: they stay bf16); the
  conv's taps, A_log, dt_bias and the norm are under a thousandth of
  that and left out, as peaks.py leaves the scales out;
* every live stream's recurrent state, READ and WRITTEN once: a head's
  matrix dv x dk for H heads and the conv's last `linear_conv_kernel_dim`
  - 1 inputs of 2 x H dk + H dv channels (q, k and v pass the conv), two
  bytes a value, the state being held in the model's dtype
  (`state_bytes`);
* SEVEN operations a value of the heads' state (`state_flops`): the decay
  (1), the read S k of what the state holds for the incoming key (2), the
  rank-one update S + u k^T (2) and the readout S q (2), beside two a
  parameter and row for the projections. (A Mamba-2 state costs six: it
  is not read before it is written.)

Whatever serves it moves at least that: a kernel that kept the state in
fast memory over a block's steps would beat the count, and the count
would say so (a share over 100 %). `gdn_least_seconds` (read by
`gdn_roofline`) is the sum for the mixers alone. servebench/peaks.py does
NOT know the kind yet: it reads a layer that is not "mamba" as attention
(PERF.md section 7 has what that misreads for such a file, by hand).

stdlib only.
"""
from __future__ import annotations

import math
import re
from typing import Dict

from servebench.peaks import least_seconds

#: bf16: what a slot keeps between steps, and the a and b projections
STATE_BYTES = 2.0


def is_linear(config: Dict, layer: int) -> bool:
    """Whether layer `layer` of the configuration is a Gated DeltaNet
    mixer: `layer_types[layer] == "linear_attention"`; a file without
    the list has none."""
    kinds = config.get("layer_types") or []
    return layer < len(kinds) and kinds[layer] == "linear_attention"


def linear_layers(config: Dict) -> int:
    """Layers of the configuration AS RUN that are such mixers."""
    if "linear_num_value_heads" not in config:
        return 0
    return sum(is_linear(config, l)
               for l in range(config["num_hidden_layers"]))


def sizes(config: Dict) -> Dict[str, int]:
    """H; all heads' keys (and queries) and values; the channels through
    the conv (q, k and v); the wide in-projection's outputs (q, k, v, z)."""
    H = config["linear_num_value_heads"]
    key = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    value = H * config["linear_value_head_dim"]
    return {"heads": H, "key": key, "value": value,
            "conv": 2 * key + value, "proj": 2 * key + 2 * value}


def proj_params(config: Dict) -> int:
    """Parameters of one mixer's five wide projections: q, k, v, the
    gate z and the output."""
    s = sizes(config)
    return config["hidden_size"] * (s["proj"] + s["value"])


def ab_params(config: Dict) -> int:
    """Parameters of the two head-wide projections (decay and beta)."""
    return config["hidden_size"] * 2 * sizes(config)["heads"]


def heads_state(config: Dict) -> int:
    """Values of the heads' state one stream keeps for one such layer."""
    return (config["linear_num_value_heads"] * config["linear_value_head_dim"]
            * config["linear_key_head_dim"])


def state_values(config: Dict) -> int:
    """Values one stream keeps for one such layer: the heads' state and
    the conv's tail."""
    return heads_state(config) \
        + (config["linear_conv_kernel_dim"] - 1) * sizes(config)["conv"]


def state_bytes(config: Dict) -> float:
    """Bytes one step moves for one stream's state in one such layer:
    read once and written once."""
    return state_values(config) * STATE_BYTES * 2.0


def state_flops(config: Dict) -> float:
    """Operations one position costs over one layer's heads' state."""
    return 7.0 * heads_state(config)


def weight_bytes(config: Dict) -> float:
    """Bytes of one mixer's weights a step streams: the wide projections
    at the configuration's width, a and b in bf16."""
    wide = 1.0 if config["serve"].get("quant") == "int8" else 2.0
    return proj_params(config) * wide + ab_params(config) * STATE_BYTES


def gdn_least_seconds(config: Dict, device_kind: str, chips: int,
                      steps: int, live_streams: float) -> Dict[str, float]:
    """The least time `chips` chips could take for the Gated DeltaNet
    mixers of one block of `steps` decode steps with `live_streams` live
    streams: per such layer and step the projections once and every live
    stream's state read and written."""
    Ls = linear_layers(config)
    by = steps * Ls * (weight_bytes(config)
                       + live_streams * state_bytes(config))
    fl = steps * Ls * live_streams * (
        2.0 * (proj_params(config) + ab_params(config))
        + state_flops(config))
    return least_seconds(by, fl, device_kind, chips)


# -- the mixers' operations in a device trace --------------------------------
#
# The mixers are XLA's own operations in the program this file was written
# beside, and a trace names an operation by its HLO text: the
# instruction's name and the shape of its result (servebench/xplane.py
# keeps the first 64 characters, every character outside [A-Za-z0-9_.:-]
# as `_`). So they are told by the shapes only they produce, from the
# configuration file: a dim of the wide in-projection (q | k | v | z), of
# the conv's channels, of all heads' values or of all heads' keys (17,280,
# 11,520, 5,760 and 2,880 in the published file: no other layer has such
# a width), a result of [.., H, dv] or [.., H, dk] (a head's readout, its
# norm, its normalised keys), and the state's minor dims: g heads' values
# share a row of lanes where g x dv is the least whole number of 128
# (ops over [.., H/g, dk, g dv], the reduction over dk to [.., H/g, g dv]),
# else a head's own [.., H, dk, dv]; and a chunk's solve, whatever the
# number c of positions the program solves at once: [H, c, c], [H, c, dk],
# [H, c, dv] (an attention layer's chunk is [heads, c, positions cached],
# which is none of them). A shape that ends in dk, or in c, must END
# there (the cleaned name has `__` behind a shape's last dim, or is cut
# off): a step of as many rows as dk (this cell's mixed step: 64 + 32 =
# 96) gives an attention layer results of [heads, rows, head_dim], which
# go on. The out-projection's RESULT is
# [rows, hidden], as every other layer's is, and is not caught; PERF.md
# (section 5) lists the names a traced run showed and what share of the
# path they are.

def gdn_patterns(config: Dict):
    """A compiled pattern over a trace's cleaned operation names."""
    s = sizes(config)
    dims = "|".join(str(s[k]) for k in ("proj", "conv", "value", "key"))
    H, dk, dv = (config[k] for k in (
        "linear_num_value_heads", "linear_key_head_dim",
        "linear_value_head_dim"))
    g = 128 // math.gcd(dv, 128)
    if H % g:
        g = 1
    J, L = H // g, g * dv
    return re.compile(rf"(?<![0-9])(?:{dims})(?![0-9])"
                      rf"|_{J}_{dk}_{L}_|_{J}_{L}_"
                      rf"|_{H}_{dv}_|_{H}_{dk}_(?:_|$)"
                      rf"|_{H}_(\d+)_(?:\1|{dv}|{dk})_(?:_|$)")


def gdn_op_seconds(ctx):
    """Self seconds of the mixers' operations in the trace, or None where
    there is no trace or the configuration has no such layer."""
    ops = (ctx.trace or {}).get("ops")
    if not ops or not linear_layers(ctx.config):
        return None
    pat = gdn_patterns(ctx.config)
    return sum(sec for name, sec, _ in ops if pat.search(name))
