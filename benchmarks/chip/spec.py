"""What a cell is made of, read from files found by name.

``BENCHMARK.json`` (at the root of the checkout) names each cell's
configuration and traffic mix.  The harness then reads, all under
:data:`FILES` (this directory):

- ``configs/<config>.json``: the model as it is run, in the keys of its
  published ``config.json``, with the butterfly split it is served with;
- ``arch/<model_type>.py``: the architecture that the configuration's
  ``model_type`` names (below);
- ``traffic/<mix>.json``: arrivals, lengths, transport, link and slots;
- ``checks/<cell>.json``: the limit that decides ``correct`` and the size of
  the sample it is judged on;
- ``links.json`` and ``peaks.json``: the link model and the chip's peaks.

A new cell adds files and a ``workloads`` entry; it edits none of these.  A
configuration of an architecture that has no module yet brings
``arch/<model_type>.py`` beside its configuration file.  A ``model_type``
with no module is refused when its configuration loads.

The interface of an architecture module, which the harness reaches through
:func:`arch_module` and nothing else:

``Dims``
    A frozen, hashable dataclass of the model's dims (``weights`` keys its
    compiled draws on it).  The harness reads only ``name``, ``arch`` (the
    ``model_type``), ``num_layers``, ``d_model``, ``vocab``, ``rms_eps``,
    ``split`` (the butterfly after this many layers), ``d_r`` and
    ``wire_bits``; the rest are the module's own.
``dims(name, config) -> Dims``
    The dims of configuration file ``name``; raises ``ValueError`` for keys
    the module cannot serve.
``LEAF_IDS``
    Leaf name -> key index, for every leaf of the layers and of the global
    leaves.  Never renumber one: each seed would draw new weights.  Indices
    14 and 15 are the butterfly's (``weights.BUTTERFLY_IDS``).
``layer_leaves(m, layer) -> {name: shape}``
    Leaves of layer ``layer``.  Layers may be of different kinds; each leaf
    is stacked over the layers that have it, in layer order.
``global_leaves(m) -> {name: shape}``
    Leaves outside the layers, shared blocks among them.  They hold
    ``embed`` (vocab, d) and ``final_norm`` (d,), and ``head`` (vocab, d)
    unless the head is tied to ``embed``.  The butterfly's leaves are added
    by ``weights``.  A leaf named ``*norm``, ``norm1`` or ``norm2`` is a gain
    offset, ``embed`` and ``head`` are (vocab, d), and any other is an
    (..., in, out) matrix (``weights._leaf``).
``program_config(m)``
    The program's ``repro.configs.base.ModelConfig`` for these dims.
``program_tree(w, m)``
    The program's parameter tree (``repro.models.model.init_model``'s
    layout) from the drawn weights ``w``, sharing its arrays.
``forward(seed, m, xs, control) -> xs``
    The reference's pass over every layer, with the butterfly wire
    (``reference.wire_rows``) after layer ``split - 1``: ``xs`` holds one
    float32 (S, d) array of embedded tokens per sequence, the result the
    residual after the last layer.  Whatever the architecture carries beside
    the residual stays inside it.  Weights come from ``weights.draw_layer``
    and ``draw_global``, numerics from ``reference``.
``edge_prefill(m, seq)``, ``cloud_prefill(m, seq)``, ``edge_step(m, pos)``, \
``cloud_step(m, pos)``, ``decode_token(m, pos)``
    Model FLOPs of the programs the window drives (``work.py`` says what
    they count); ``layer_params(m)`` too, where every layer is alike.
"""
from __future__ import annotations

import importlib.util
import json
import re
import sys
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
FILES = HERE        # where configurations, architectures, traffic are found

INTERFACE = ("Dims", "dims", "LEAF_IDS", "layer_leaves", "global_leaves",
             "program_config", "program_tree", "forward", "edge_prefill",
             "cloud_prefill", "edge_step", "cloud_step", "decode_token")
_ARCHS: dict = {}


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    model: object       # an architecture module's Dims
    traffic: dict
    check: dict


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def arch_module(model_type: str):
    """The module of architecture ``model_type``: ``arch/<model_type>.py``
    under :data:`FILES`, loaded once per path."""
    path = FILES / "arch" / f"{model_type}.py"
    if path in _ARCHS:
        return _ARCHS[path]
    if not isinstance(model_type, str) or \
            not re.fullmatch(r"[A-Za-z0-9_]+", model_type) or \
            not path.is_file():
        raise ValueError(f"model_type {model_type!r} has no architecture "
                         f"module: looked for {path}")
    name = f"arch_{model_type}"
    s = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(s)
    sys.modules[name] = mod          # dataclasses look their module up
    s.loader.exec_module(mod)
    missing = [n for n in INTERFACE if not hasattr(mod, n)]
    if missing:
        del sys.modules[name]
        raise ValueError(f"{path} lacks {', '.join(missing)} of the "
                         f"architecture interface")
    _ARCHS[path] = mod
    return mod


def __getattr__(name):
    # ``Model``: the dims of the architecture the benchmark began with,
    # under the name that tests and tools build them by
    if name == "Model":
        return arch_module("qwen3").Dims
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def model_from_config(name: str, c: dict):
    """Dims from a configuration file (published ``config.json`` keys), by
    the module of its ``model_type``."""
    try:
        mod = arch_module(c.get("model_type"))
    except ValueError as e:
        raise ValueError(f"{name}: {e}") from None
    return mod.dims(name, c)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    config = _json(FILES / "configs" / f"{w['config']}.json")
    return Cell(name=name, chips=w["chips"],
                model=model_from_config(w["config"], config),
                traffic=_json(FILES / "traffic" / f"{w['traffic']}.json"),
                check=_json(FILES / "checks" / f"{name}.json"))


def _metrics(kind: str, name: str, root: Path) -> list:
    bench = _json(root / "BENCHMARK.json")
    return [m for m in bench[kind] if name in m.get("workloads", [name])]


def per_layer_metrics(name: str, root: Path = ROOT) -> list:
    """Names of the per-layer metrics that this cell reports."""
    return [m["name"] for m in _metrics("per_layer", name, root)]


def end_to_end_metrics(name: str, root: Path = ROOT) -> dict:
    """Unit of each end-to-end metric that this cell reports, by name."""
    return {m["name"]: m["unit"] for m in _metrics("end_to_end", name, root)}


def link(name: str) -> dict:
    links = _json(HERE / "links.json")
    if name not in links:
        raise KeyError(f"unknown link {name!r}; known: {sorted(links)}")
    return links[name]


def peaks(device_kind: str) -> dict:
    table = _json(HERE / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(k for k in table if not k.startswith('_'))}")
    return table[device_kind]
