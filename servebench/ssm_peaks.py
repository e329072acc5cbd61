"""A Mamba-2 mixer, counted from a configuration file: what a model with
state-space layers (`layer_types` and the `mamba_*` keys) must move to
advance every live stream by one position in one such layer, and how a
device trace tells the mixers' operations.

It counts the work of the MODEL, not of an implementation: per Mamba
layer and decode step,

* the mixer's two projections, once: hidden x (2 x Di + 2 x G x N + Nh)
  in (z, x, B, C and dt) and Di x hidden out, Di = `mamba_n_heads` x
  `mamba_d_head` (`proj_params`; the conv's 4 taps, A, D, dt_bias and
  the norm are under a thousandth of that and left out, as peaks.py
  leaves the scales out);
* every live stream's recurrent state, READ and WRITTEN once: a head's
  state `mamba_n_heads` x `mamba_d_head` x `mamba_d_state` and the
  conv's last `mamba_d_conv` - 1 inputs of Di + 2 x G x N channels, two
  bytes a value, the state being held in the model's dtype
  (`state_bytes`);
* the decay, the outer product, the sum and the readout over a head's
  state, six operations a value (`state_flops`), beside two a parameter
  and row for the projections.

Whatever serves it moves at least that: a kernel that kept the state in
fast memory over a block's steps would beat the count, and the count
would say so (a share over 100 %). These are the ONLY formulas of a
Mamba layer in the benchmark: `servebench/peaks.py` adds them up, for the
mixers alone (`ssm_least_seconds`, read by `ssm_roofline`) and for the
whole step (`block_least_seconds`, read by `block_roofline`).

stdlib only.
"""
from __future__ import annotations

import re
from typing import Dict

#: bf16: what a slot keeps between steps
STATE_BYTES = 2.0


def is_mamba(config: Dict, layer: int) -> bool:
    """Whether layer `layer` of the configuration is a Mamba-2 mixer:
    `layer_types[layer] == "mamba"`; a file without the list has none."""
    kinds = config.get("layer_types") or []
    return layer < len(kinds) and kinds[layer] == "mamba"


def mamba_layers(config: Dict) -> int:
    """Layers of the configuration AS RUN that are Mamba-2 mixers."""
    return sum(is_mamba(config, l) for l in range(config["num_hidden_layers"]))


def sizes(config: Dict) -> Dict[str, int]:
    """Di (the inner stream), Dc (the channels through the conv) and P
    (the in-projection's outputs) of one mixer."""
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    groups = 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    return {"inner": inner, "conv": inner + groups,
            "proj": 2 * inner + groups + config["mamba_n_heads"]}


def proj_params(config: Dict) -> int:
    """Parameters of one mixer's in- and out-projection."""
    s = sizes(config)
    return config["hidden_size"] * (s["proj"] + s["inner"])


def heads_state(config: Dict) -> int:
    """Values of the heads' state one stream keeps for one Mamba layer."""
    return (config["mamba_n_heads"] * config["mamba_d_head"]
            * config["mamba_d_state"])


def state_values(config: Dict) -> int:
    """Values one stream keeps for one Mamba layer: the heads' state and
    the conv's tail."""
    return heads_state(config) \
        + (config["mamba_d_conv"] - 1) * sizes(config)["conv"]


def state_bytes(config: Dict) -> float:
    """Bytes one step moves for one stream's state in one Mamba layer:
    read once and written once."""
    return state_values(config) * STATE_BYTES * 2.0


def state_flops(config: Dict) -> float:
    """Operations one position costs over one layer's heads' state."""
    return 6.0 * heads_state(config)


# -- the mixers' operations in a device trace --------------------------------
#
# The mixers are XLA's own operations, and a trace names an operation by
# its HLO text: the instruction's name and the shape of its result
# (servebench/xplane.py keeps the first 64 characters, every character
# outside [A-Za-z0-9_.:-] as `_`). So they are told by the shapes only
# they produce, from the configuration file: a dim of P (the
# in-projection's result), of Dc (the conv's channels) or of Di (the
# gate, the norm, the out-projection's input), the state's minor dims
# [.., Nh, Hd, N], and a result that ENDS in [Nh, Hd] (the readout y of
# the state, a reduction over N in a fusion of its own: a third of the
# path's time in the first traced run). The out-projection's RESULT is
# [rows, hidden], as every other layer's is, and is not caught (3 % of
# the path there); PERF.md (section 5) lists the names a traced run
# showed and what share of the path they are.

def ssm_patterns(config: Dict):
    """A compiled pattern over a trace's cleaned operation names."""
    s = sizes(config)
    dims = "|".join(str(s[k]) for k in ("proj", "conv", "inner"))
    Nh, Hd, N = (config[k] for k in ("mamba_n_heads", "mamba_d_head",
                                     "mamba_d_state"))
    return re.compile(rf"(?<![0-9])(?:{dims})(?![0-9])"
                      rf"|_{Nh}_{Hd}_{N}_|_{Nh}_{Hd}__")


def ssm_op_seconds(ctx):
    """Self seconds of the mixers' operations in the trace, or None where
    there is no trace or the configuration has no Mamba layer."""
    ops = (ctx.trace or {}).get("ops")
    if not ops or "mamba_n_heads" not in ctx.config \
            or not mamba_layers(ctx.config):
        return None
    pat = ssm_patterns(ctx.config)
    return sum(sec for name, sec, _ in ops if pat.search(name))
