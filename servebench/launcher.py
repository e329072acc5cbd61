#!/usr/bin/env python3
"""Start `butterfly serve` for one configuration file.

    python3 servebench/launcher.py --config servebench/configs/<name>.json --port P

The system under test is reached through its real entry point,
`butterfly_tpu.serve.cli.main(["serve", ...])`, so `run_server`'s own
weight building and warm-up are what a run measures as set-up. All this
file adds is the cell's model: the sizes of the configuration file,
registered as a preset under the configuration's name, and the
deployment's flags from the file's "serve" group.

This is the only process of the benchmark that imports JAX on the chip.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
#: exit code when JAX finds no TPU, or fewer chips than the cell asks for
NO_CHIP = 4

#: key of a Hugging Face `config.json` -> field of the program's ModelConfig
HF_TO_MODEL = {
    "vocab_size": "vocab_size", "hidden_size": "hidden_size",
    "num_hidden_layers": "num_layers", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "intermediate_size",
    "max_position_embeddings": "max_seq_len", "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
    "hidden_act": "act",
}
#: `model_type` of the source -> the program's architecture family
ARCH = {"mistral": "llama", "llama": "llama", "mixtral": "mixtral"}


def model_fields(config: dict) -> dict:
    """The program's ModelConfig fields for a configuration file."""
    if config.get("sliding_window") is not None:
        raise ValueError("the program has no sliding-window attention")
    out = {dst: config[src] for src, dst in HF_TO_MODEL.items()
           if src in config}
    out["arch"] = ARCH[config["model_type"]]
    for k in ("num_local_experts", "num_experts_per_tok"):
        if k in config:
            out[{"num_local_experts": "num_experts"}.get(k, k)] = config[k]
    dtype = config.get("torch_dtype", "bfloat16")
    out["dtype"] = dtype
    if dtype == "float32":
        out["param_dtype"] = "float32"
    return out


def serve_argv(config: dict, port: int) -> list:
    argv = ["serve", "--model", config["name"], "--host", "127.0.0.1",
            "--port", str(port)]
    for k, v in config["serve"].items():
        argv += ["--" + k.replace("_", "-"), str(v)]
    return argv


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--require-tpu", type=int, default=0, metavar="CHIPS",
                    help="exit with code 4 before building anything unless "
                         "JAX shows a TPU with this many chips (0: any backend)")
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT))
    if args.require_tpu:
        import jax
        devs = jax.devices()
        if devs[0].platform != "tpu" or len(devs) < args.require_tpu:
            print(f"servebench: need {args.require_tpu} TPU chip(s); JAX found "
                  f"platform {devs[0].platform!r}, {len(devs)} device(s)",
                  file=sys.stderr, flush=True)
            return NO_CHIP
    config = json.loads(Path(args.config).read_text())
    from butterfly_tpu.core.config import PRESETS, ModelConfig
    from butterfly_tpu.serve import cli
    fields = model_fields(config)
    PRESETS[config["name"]] = lambda: ModelConfig(**fields)
    return cli.main(serve_argv(config, args.port))


if __name__ == "__main__":
    sys.exit(main())
