"""End-to-end system tests: serving engine, train loop w/ resume,
partition-spec/param tree coherence, multi-device pjit subprocess."""
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_mesh
from repro.models.common import InitMaker, QuantMaker
from repro.models import transformer as T


def test_serving_engine_generates():
    from repro.serve import ServeConfig, ServingEngine
    cfg = get_config("granite-8b", smoke=True)
    params = T.build_params(cfg, QuantMaker(jax.random.PRNGKey(0)))
    eng = ServingEngine(cfg, params, ServeConfig(max_len=48))
    batch = {"tokens": np.random.default_rng(0).integers(
        1, cfg.vocab, (2, 8)).astype(np.int32)}
    out = eng.generate(batch, max_new_tokens=6)
    assert out["generated"].shape == (2, 6)
    assert (out["generated"] >= 0).all() and (out["generated"] < cfg.vocab).all()


def test_serving_greedy_deterministic():
    from repro.serve import ServeConfig, ServingEngine
    cfg = get_config("granite-8b", smoke=True)
    params = T.build_params(cfg, InitMaker(jax.random.PRNGKey(0)))
    eng = ServingEngine(cfg, params, ServeConfig(max_len=32))
    batch = {"tokens": np.random.default_rng(1).integers(
        1, cfg.vocab, (2, 6)).astype(np.int32)}
    a = eng.generate(batch, max_new_tokens=4)["generated"]
    b = eng.generate(batch, max_new_tokens=4)["generated"]
    np.testing.assert_array_equal(a, b)


@pytest.mark.slow
def test_train_resume_bit_identical():
    from repro.launch.train import train
    with tempfile.TemporaryDirectory() as d1, \
            tempfile.TemporaryDirectory() as d2:
        ref = train("whisper-medium", smoke=True, steps=12, batch_size=2,
                    seq_len=16, ckpt_dir=d1, ckpt_every=4, log_every=100)
        try:
            train("whisper-medium", smoke=True, steps=12, batch_size=2,
                  seq_len=16, ckpt_dir=d2, ckpt_every=4, log_every=100,
                  fail_at=6)
        except RuntimeError:
            pass
        res = train("whisper-medium", smoke=True, steps=12, batch_size=2,
                    seq_len=16, ckpt_dir=d2, ckpt_every=4, log_every=100)
        assert abs(ref["final_loss"] - res["final_loss"]) < 1e-6


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_match_tree(arch):
    """PartitionSpec tree has exactly the parameter tree's structure, for
    both the dense (train) and quantized (serve) parameterizations."""
    from repro.runtime import partitioning as PT
    from repro.launch.steps import abstract_params
    mesh = make_mesh((1, 1), ("data", "model"))
    for train_mode in (True, False):
        params = abstract_params(get_config(arch), quantize=not train_mode)
        specs = PT.param_specs(get_config(arch), mesh, train=train_mode)
        t1 = jax.tree_util.tree_structure(params)
        t2 = jax.tree_util.tree_structure(
            specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        assert t1 == t2, f"{arch} train={train_mode}"


_SUBPROCESS_PJIT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.configs.shapes import ShapeSpec
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_cell
from repro.models.common import InitMaker
from repro.models import transformer as T
from repro.optim import AdamWConfig, adamw_init
cfg = get_config("granite-8b", smoke=True)
mesh = make_mesh((4, 2), ("data", "model"))
shape = ShapeSpec("t", 32, 8, "train")
fn, args, in_sh, out_sh, donate = build_cell(cfg, shape, mesh)
params = T.build_params(cfg, InitMaker(jax.random.PRNGKey(0)))
opt = adamw_init(params, AdamWConfig())
batch = {"tokens": jnp.ones((8, 32), jnp.int32),
         "labels": jnp.ones((8, 32), jnp.int32)}
params = jax.device_put(params, in_sh[0])
opt = jax.device_put(opt, in_sh[1])
batch = jax.device_put(batch, in_sh[2])
step = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
               donate_argnums=donate)
p2, o2, m = step(params, opt, batch)
loss = float(m["loss"])
assert np.isfinite(loss), loss
print("SUBPROCESS_OK", loss)
"""


@pytest.mark.slow
def test_pjit_train_step_runs_on_8_devices():
    """Actually EXECUTES the sharded train step on 8 host devices."""
    import os
    r = subprocess.run([sys.executable, "-c", _SUBPROCESS_PJIT],
                       capture_output=True, text=True, timeout=900,
                       env={**os.environ, "PYTHONPATH": "src"},
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "SUBPROCESS_OK" in r.stdout, r.stderr[-2000:]


def test_compile_cache_dir_env_or_fixed_checkout_path(monkeypatch, tmp_path):
    """The persistent compile cache goes where JAX_COMPILATION_CACHE_DIR
    says, else to one fixed, git-ignored directory in the checkout."""
    import os
    from repro.launch.cli import enable_compile_cache
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = enable_compile_cache()
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        with open(os.path.join(root, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
