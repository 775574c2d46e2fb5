"""The pod-axis split pipeline needs >1 device, so it runs in a subprocess
with its own XLA_FLAGS (the main pytest process must stay single-device)."""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.subprocess

CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models import model as M
from repro.serving.pipeline import make_split_pipeline, wire_stats

cfg = get_config("qwen3-8b").reduced().with_butterfly(layer=1, d_r=32)
built = M.build(cfg)
params, _ = M.init_model(jax.random.key(0), built)
mesh = jax.make_mesh((2, 1), ("pod", "data"))
Mmb, mb, S = 3, 2, 16
toks = jax.random.randint(jax.random.key(1), (Mmb*mb, S), 0, cfg.vocab_size)
pipe = jax.jit(make_split_pipeline(built, mesh, Mmb, S, mb))
logits = pipe(params, toks)
ref, _ = M.forward_train(params, built, {"tokens": toks})
err = float(jnp.max(jnp.abs(logits - ref[:, -1])))
assert err < 5e-3, err
# the fused Pallas wire kernels (interpret mode here) give the same logits
kern = jax.jit(make_split_pipeline(built, mesh, Mmb, S, mb, use_kernel=True))
kerr = float(jnp.max(jnp.abs(kern(params, toks) - logits)))
assert kerr < 5e-3, kerr
hlo = jax.jit(pipe).lower(params, toks).compile().as_text()
assert any("collective-permute" in l and "s8[" in l for l in hlo.splitlines()), \
    "wire must cross the pod boundary as int8"
stats = wire_stats(cfg, mb, S)
assert stats["compression"] > 10
print("PIPELINE_OK", err, stats["compression"])
"""


def test_split_pipeline_two_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", CODE], env=env,
                         capture_output=True, text=True, timeout=500)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "PIPELINE_OK" in res.stdout
