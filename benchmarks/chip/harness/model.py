"""The program's model configuration for a configuration file.

The file states the sizes as they are run. The program's registry entry
for ``arch`` is taken with ``n_layers`` set from the file, and every other
size the file states must equal the program's: a program whose config
drifts from the file is refused, not measured as something else.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

_TOP = ("family", "d_model", "n_layers", "n_heads", "n_kv_heads", "d_ff",
        "vocab_size", "norm", "rope_theta", "linear_bias")


def program_config(cj: Dict[str, Any]):
    from repro.configs import registry
    m = cj["model"]
    cfg = dataclasses.replace(registry.get_config(cj["arch"]),
                              n_layers=int(m["n_layers"]))
    got = {k: getattr(cfg, k) for k in _TOP if k in m}
    if "head_dim" in m:
        got["head_dim"] = cfg.resolved_head_dim
    if "ssm" in m:
        got["ssm"] = {k: getattr(cfg.ssm, k) for k in m["ssm"]}
    if "mixer" in m:
        got["mixer"] = cfg.pattern[0].mixer
        got["mlp"] = cfg.pattern[0].mlp
    got["precision"] = cfg.param_dtype
    want = {k: m[k] for k in got if k in m}
    want["precision"] = cj["precision"]
    if len(cfg.pattern) != 1 or got != want:
        raise ValueError(f"{cj['name']}: the program's config differs from "
                         f"the file: program {got} (pattern "
                         f"{cfg.pattern}), file {want}")
    return cfg
