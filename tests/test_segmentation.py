"""Property tests for the layer-stack segmentation (hypothesis): segments
must reconstruct the flat def list exactly for arbitrary patterns."""
import pytest

pytest.importorskip("hypothesis", reason="property suites need hypothesis "
                    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.models.transformer import (LayerDef, Segment, build_layer_defs,
                                      segmentize, split_defs)

kinds = st.sampled_from([
    LayerDef(mixer="attn", ffn="mlp"),
    LayerDef(mixer="attn", ffn="moe"),
    LayerDef(mixer="attn", ffn="mlp", window=128),
    LayerDef(mixer="mamba", ffn=None),
    LayerDef(mixer="mlstm", ffn=None),
    LayerDef(mixer="slstm", ffn=None),
    LayerDef(mixer="mamba", ffn=None, hybrid=0),
])


def _flatten(segments):
    out = []
    for s in segments:
        out.extend(list(s.unit) * s.repeats)
    return out


@settings(max_examples=100, deadline=None)
@given(st.lists(kinds, min_size=1, max_size=40))
def test_segmentize_reconstructs(defs):
    segs = segmentize(defs)
    assert _flatten(segs) == defs
    assert all(s.repeats >= 1 and len(s.unit) >= 1 for s in segs)


@settings(max_examples=50, deadline=None)
@given(st.lists(kinds, min_size=2, max_size=30), st.data())
def test_split_preserves_layers(defs, data):
    boundary = data.draw(st.integers(1, len(defs) - 1))
    stages = split_defs(defs, boundary)
    assert len(stages) == 2
    assert _flatten(stages[0]) == defs[:boundary]
    assert _flatten(stages[1]) == defs[boundary:]


def test_assigned_arch_patterns():
    """Spot-check the per-arch layer patterns against their cards."""
    g3 = build_layer_defs(get_config("gemma3-12b"))
    assert len(g3) == 48
    # 5 local : 1 global
    assert [d.window for d in g3[:6]] == [1024] * 5 + [None]
    zam = build_layer_defs(get_config("zamba2-7b"))
    assert len(zam) == 81
    # 13 invocations of the shared blocks, at the published hybrid_layer_ids
    # (gaps of 5 and 6), and every layer keeps its own Mamba2 mixer
    assert [i for i, d in enumerate(zam) if d.hybrid is not None] == \
        [6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77]
    assert [d.hybrid for d in zam if d.hybrid is not None] == list(range(13))
    assert sum(d.mixer == "mamba" for d in zam) == 81
    xl = build_layer_defs(get_config("xlstm-125m"))
    assert [d.mixer for d in xl[:3]] == ["mlstm", "mlstm", "slstm"]
    l4 = build_layer_defs(get_config("llama4-maverick-400b-a17b"))
    assert sum(d.ffn == "moe" for d in l4) == 24      # MoE every other layer
    qm = build_layer_defs(get_config("qwen3-moe-235b-a22b"))
    assert all(d.ffn == "moe" for d in qm) and len(qm) == 94


def test_segment_counts_small():
    """Scan-friendliness: each arch compresses to few segments."""
    for arch in ("qwen3-14b", "gemma3-12b", "xlstm-125m",
                 "qwen3-moe-235b-a22b", "llama4-maverick-400b-a17b"):
        segs = segmentize(build_layer_defs(get_config(arch)))
        assert len(segs) <= 3, (arch, len(segs))
    # zamba2's invocations are each their own (no two are alike): each is a
    # one-layer segment, and each run of plain Mamba2 layers between them
    # one scanned segment, never unrolled into a longer unit
    segs = segmentize(build_layer_defs(get_config("zamba2-7b")))
    assert len(segs) == 27 and all(len(s.unit) == 1 for s in segs)
    assert [s.repeats for s in segs[:4]] == [6, 1, 4, 1]
