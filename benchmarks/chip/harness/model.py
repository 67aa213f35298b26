"""The program's model configuration for a configuration file.

The file's ``model`` group states the sizes as they are run. The program's
registry entry for ``arch`` is taken with ``n_layers`` set from the file,
and every other key the file states is compared with the program config's
field of the same name, each sub-group (``ssm``, ``moe``, any later one)
key by key: a program whose config drifts from the file, or that has no
field for a key the file states, is refused, not measured as something
else. ``head_dim`` is compared with the program's resolved head width and
the file's ``precision`` with the parameters' dtype.

The layers are given by ``pattern``, a list of ``{"mixer", "mlp"[,
"dense_residual"]}`` in period order, or, for a period of one layer, by
``mixer`` and ``mlp`` in the group itself. ``n_layers`` is split over the
period as the program's ``ModelConfig.groups`` splits it.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List


def pattern(m: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The file's layer specs in period order, ``dense_residual`` False
    where a spec does not state it."""
    if "pattern" in m:
        if "mixer" in m or "mlp" in m:
            raise ValueError("the model group states both a pattern and a "
                             "top-level mixer or mlp")
        specs = m["pattern"]
    else:
        specs = [{"mixer": m["mixer"], "mlp": m["mlp"]}]
    return [{"dense_residual": False, **s} for s in specs]


def layers(m: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The spec of each of the ``n_layers`` layers, in order."""
    p = pattern(m)
    return [p[i % len(p)] for i in range(int(m["n_layers"]))]


def _same(have: Any, want: Any) -> bool:
    if isinstance(have, bool) != isinstance(want, bool):
        return False
    if isinstance(have, (list, tuple)) and isinstance(want, (list, tuple)):
        return len(have) == len(want) and all(
            _same(a, b) for a, b in zip(have, want))
    return have == want


def _differences(want: Dict[str, Any], have: Any, where: str) -> List[str]:
    """What ``want`` (a group of the file) states that the dataclass
    ``have`` does not hold."""
    fields = {f.name for f in dataclasses.fields(have)}
    out = []
    for key, value in want.items():
        name = where + key
        if key not in fields:
            out.append(f"{name}: the program has no field for it")
            continue
        got = getattr(have, key)
        if isinstance(value, dict):
            if not dataclasses.is_dataclass(got):
                out.append(f"{name}: the program has no {key} group "
                           f"({got!r})")
            else:
                out.extend(_differences(value, got, name + "."))
        elif not _same(got, value):
            out.append(f"{name}: program {got!r}, file {value!r}")
    return out


def _pattern_differences(m: Dict[str, Any], cfg) -> List[str]:
    specs = pattern(m)
    if len(specs) != len(cfg.pattern):
        return [f"pattern: program {len(cfg.pattern)} layer specs "
                f"{cfg.pattern}, file {len(specs)}"]
    return [d for i, (spec, have) in enumerate(zip(specs, cfg.pattern))
            for d in _differences(spec, have, f"pattern[{i}].")]


def program_config(cj: Dict[str, Any]):
    """The program's config for the file ``cj``, or ValueError naming
    every key in which the two differ."""
    from repro.configs import registry
    m = cj["model"]
    cfg = dataclasses.replace(registry.get_config(cj["arch"]),
                              n_layers=int(m["n_layers"]))
    rest = {k: v for k, v in m.items()
            if k not in ("pattern", "mixer", "mlp", "head_dim")}
    diffs = _pattern_differences(m, cfg) + _differences(rest, cfg, "")
    if "head_dim" in m and not _same(cfg.resolved_head_dim, m["head_dim"]):
        diffs.append(f"head_dim: program {cfg.resolved_head_dim!r}, file "
                     f"{m['head_dim']!r}")
    if cfg.param_dtype != cj["precision"]:
        diffs.append(f"precision: program {cfg.param_dtype!r}, file "
                     f"{cj['precision']!r}")
    if diffs:
        raise ValueError(f"{cj['name']}: the program's config differs from "
                         f"the file: " + "; ".join(diffs))
    return cfg
