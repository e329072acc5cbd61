"""A Mamba-1 mixer, counted from a configuration file: what a model of
the `jamba` family (the `mamba_expand` / `mamba_d_state` / `mamba_dt_rank`
keys beside `attn_layer_period` / `attn_layer_offset`) must move to
advance every live stream by one position in one such layer, and how a
device trace tells the mixers' operations.

It counts the work of the MODEL (Gu and Dao, "Mamba", arXiv:2312.00752,
with the family's dt-projection), not of an implementation: per Mamba
layer and decode step,

* the mixer's four projections, once: hidden x 2 Di in (u and the gate
  z), Di x (R + 2 N) (dt's bottleneck, B and C, from the conv's output),
  R x Di (dt a channel) and Di x hidden out, Di = `mamba_expand` x
  `hidden_size`, N = `mamba_d_state`, R = `mamba_dt_rank`
  (`proj_params`), the in- and out-projection a byte a parameter where
  the configuration serves int8 codes, else two, the two small ones two
  whatever the others are (they stay bf16); the conv's taps and bias,
  A_log, D, dt's bias and the three inner norms are three thousandths of
  that and left out, as peaks.py leaves the scales out;
* every live stream's recurrent state, READ and WRITTEN once: N numbers
  a channel for Di channels and the conv's last `mamba_d_conv` - 1
  inputs of Di channels (u alone passes the conv), two bytes a value,
  the state being held in the model's dtype (`state_bytes`);
* SEVEN operations a value of the channels' state (`state_flops`), one
  of them an EXPONENTIAL: dt[c] x A[c, n] (1), its exponential (1), the
  decay times h (1), (dt u)[c] x B[n] (1), their sum (1), and the
  readout h x C[n] summed over n (2); beside two a parameter and row for
  the projections. (A Mamba-2 state costs six and one exponential a
  HEAD: here every state value has a rate of its own.)

Whatever serves it moves at least that: a kernel that kept the state in
fast memory over a block's steps would beat the count, and the count
would say so (a share over 100 %). `mamba1_least_seconds` (read by
`mamba1_roofline`) is the sum for the mixers alone. servebench/peaks.py
does NOT know the kind: the file has no `layer_types`, so it reads every
layer as attention (PERF.md section 7 has what that misreads, by hand).

stdlib only.
"""
from __future__ import annotations

import re
from typing import Dict

from servebench.peaks import least_seconds

#: bf16: what a slot keeps between steps, and the two small projections
STATE_BYTES = 2.0


def is_mamba1(config: Dict, layer: int) -> bool:
    """Whether layer `layer` of the configuration is a Mamba-1 mixer:
    the family's rule, every layer but those where layer %
    `attn_layer_period` == `attn_layer_offset`; a file without
    `mamba_dt_rank` has none."""
    if "mamba_dt_rank" not in config:
        return False
    return layer % config["attn_layer_period"] != config["attn_layer_offset"]


def mamba1_layers(config: Dict) -> int:
    """Layers of the configuration AS RUN that are such mixers."""
    return sum(is_mamba1(config, l)
               for l in range(config["num_hidden_layers"]))


def sizes(config: Dict) -> Dict[str, int]:
    """Di; the state a channel; dt's rank; the in-projection's outputs
    (u | z); the x-projection's (r | B | C)."""
    inner = config["mamba_expand"] * config["hidden_size"]
    N, R = config["mamba_d_state"], config["mamba_dt_rank"]
    return {"inner": inner, "state": N, "rank": R, "proj": 2 * inner,
            "xproj": R + 2 * N}


def wide_params(config: Dict) -> int:
    """Parameters of one mixer's in- and out-projection."""
    s = sizes(config)
    return config["hidden_size"] * (s["proj"] + s["inner"])


def small_params(config: Dict) -> int:
    """Parameters of the x-projection and the dt-projection."""
    s = sizes(config)
    return s["inner"] * (s["xproj"] + s["rank"])


def proj_params(config: Dict) -> int:
    """Parameters of one mixer's four projections."""
    return wide_params(config) + small_params(config)


def channels_state(config: Dict) -> int:
    """Values of the channels' state one stream keeps for one layer."""
    s = sizes(config)
    return s["inner"] * s["state"]


def state_values(config: Dict) -> int:
    """Values one stream keeps for one such layer: the channels' state
    and the conv's tail."""
    return channels_state(config) \
        + (config["mamba_d_conv"] - 1) * sizes(config)["inner"]


def state_bytes(config: Dict) -> float:
    """Bytes one step moves for one stream's state in one such layer:
    read once and written once."""
    return state_values(config) * STATE_BYTES * 2.0


def state_flops(config: Dict) -> float:
    """Operations one position costs over one layer's channels' state."""
    return 7.0 * channels_state(config)


def state_exps(config: Dict) -> float:
    """Exponentials among them: one a state value."""
    return float(channels_state(config))


def weight_bytes(config: Dict) -> float:
    """Bytes of one mixer's weights a step streams: the in- and
    out-projection at the configuration's width, the two small
    projections in bf16."""
    wide = 1.0 if config["serve"].get("quant") == "int8" else 2.0
    return wide_params(config) * wide + small_params(config) * STATE_BYTES


def mamba1_least_seconds(config: Dict, device_kind: str, chips: int,
                         steps: int, live_streams: float) -> Dict[str, float]:
    """The least time `chips` chips could take for the Mamba-1 mixers of
    one block of `steps` decode steps with `live_streams` live streams:
    per such layer and step the projections once and every live
    stream's state read and written."""
    Lm = mamba1_layers(config)
    by = steps * Lm * (weight_bytes(config)
                       + live_streams * state_bytes(config))
    fl = steps * Lm * live_streams * (
        2.0 * proj_params(config) + state_flops(config))
    return least_seconds(by, fl, device_kind, chips)


# -- the mixers' operations in a device trace --------------------------------
#
# The mixers are XLA's own operations in the program this file was written
# beside, and a trace names an operation by its HLO text: the
# instruction's name and the shape of its result (servebench/xplane.py
# keeps the first 64 characters, every character outside [A-Za-z0-9_.:-]
# as `_`). So they are told by the shapes only they produce, from the
# configuration file: a dim of the in-projection's result (u | z: 10,240
# in the published file) or of Di (5,120: the conv, dt, the gate, the
# out-projection's input and the state's minor dims [.., N, Di], held
# with the channels last); no other layer has such a width, and an
# instruction's own number (`fusion.5120`, behind a `.`) is no dim. The
# x-projection's result ENDS in R + 2 N (192) and dt's bottleneck in R
# (160): a result of two dims or more whose LAST is one of them (the
# cleaned name has `__` behind a shape's last dim, or is cut off). They
# must end there: this cell's mixed step has 128 + 32 = 160 ROWS, so 160
# leads the result of every layer's operations in a mixed block, and a
# vector of a number a row is [160]. B and C ([.., N]: 16, a page's
# rows) are let go, a few microseconds a layer. The out-projection's
# RESULT is [rows, hidden], as every other layer's is, and is not
# caught; PERF.md (section 5) lists the names a traced run showed and
# what share of the path they are.

def mamba1_patterns(config: Dict):
    """A compiled pattern over a trace's cleaned operation names."""
    s = sizes(config)
    return re.compile(rf"(?<![0-9.])(?:{s['proj']}|{s['inner']})(?![0-9])"
                      rf"|_\d+_(?:{s['xproj']}|{s['rank']})_(?:_|$)")


def mamba1_op_seconds(ctx):
    """Self seconds of the mixers' operations in the trace, or None where
    there is no trace or the configuration has no such layer."""
    ops = (ctx.trace or {}).get("ops")
    if not ops or not mamba1_layers(ctx.config):
        return None
    pat = mamba1_patterns(ctx.config)
    return sum(sec for name, sec, _ in ops if pat.search(name))
