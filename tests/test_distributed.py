"""Distribution-layer tests.

Multi-device behavior needs >1 device, and jax locks the device count at
first init, so these tests run small subprocess scripts with
``--xla_force_host_platform_device_count=8`` and assert on their output.
In-process tests cover the sharding-rule logic (pure functions of mesh/shape).
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(script: str, timeout=560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


# ------------------------------------------------------ sharding rules


def _mk_mesh():
    from repro.launch.mesh import make_host_mesh

    return make_host_mesh()


def test_param_shardings_cover_every_leaf():
    from repro.configs import get_config
    from repro.launch.shapes import opt_specs, params_specs
    from repro.parallel.sharding import param_shardings

    mesh = _mk_mesh()
    for arch in ["deepseek-v2-lite-16b", "mamba2-1.3b", "recurrentgemma-9b"]:
        cfg = get_config(arch)
        p = params_specs(cfg)
        sh = param_shardings(p, cfg, mesh)
        assert len(jax.tree.leaves(sh)) == len(jax.tree.leaves(p))
        o = opt_specs(p)
        osh = param_shardings(o, cfg, mesh)
        assert len(jax.tree.leaves(osh)) == len(jax.tree.leaves(o))


def test_sharding_divisibility_never_violated():
    """Every spec axis assignment divides the corresponding dim (checked on
    a fake 16x16 mesh via the spec structure, not device placement)."""
    from jax.sharding import Mesh
    from repro.configs import ARCHS
    from repro.launch.shapes import params_specs
    from repro.parallel.sharding import param_shardings

    devs = np.array(jax.devices() * 256)[:256].reshape(16, 16)
    mesh = Mesh(devs, ("data", "model"))
    sizes = {"data": 16, "model": 16, "pod": 2}
    for arch, cfg in ARCHS.items():
        p = params_specs(cfg)
        sh = param_shardings(p, cfg, mesh)

        def check(path, leaf_sh, leaf):
            spec = leaf_sh.spec
            for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 9):
                if ax is None:
                    continue
                axes = (ax,) if isinstance(ax, str) else ax
                total = int(np.prod([sizes[a] for a in axes]))
                assert dim % total == 0, (arch, path, leaf.shape, spec)

        jax.tree_util.tree_map_with_path(check, sh, p)


def test_batch_sharding_drops_axes_when_indivisible():
    from jax.sharding import Mesh
    from repro.configs import get_config
    from repro.parallel.sharding import batch_shardings

    devs = np.array(jax.devices() * 256)[:256].reshape(16, 16)
    mesh = Mesh(devs, ("data", "model"))
    cfg = get_config("qwen3-0.6b")
    sh = batch_shardings(
        {"token": jax.ShapeDtypeStruct((1, 1), np.int32)}, cfg, mesh
    )
    assert sh["token"].spec == jax.sharding.PartitionSpec(None, None)


# ------------------------------------------------- multi-device (subproc)


def test_distributed_knn_matches_brute_8dev():
    out = run_sub(
        """
import jax, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core.distributed import make_distributed_knn
from repro.core.brute import brute_knn

devs = np.array(jax.devices()).reshape(2, 4)
mesh = Mesh(devs, ("data", "model"))
rng = np.random.default_rng(0)
pts = rng.normal(size=(512, 3)).astype(np.float32)
qs = rng.normal(size=(64, 3)).astype(np.float32)
qid = np.full((64,), -1, np.int32)

fn = jax.jit(make_distributed_knn(mesh, 5, use_kernel=False))
d2, idx, cnt = fn(
    jax.device_put(pts, NamedSharding(mesh, P("model", None))),
    jax.device_put(qs, NamedSharding(mesh, P("data", None))),
    jax.device_put(qid, NamedSharding(mesh, P("data"))),
)
bd, bi, _ = brute_knn(pts, 5, queries=qs)
ok = np.allclose(np.sqrt(np.asarray(d2)), np.asarray(bd), rtol=1e-4, atol=1e-5)
print("MATCH", bool(ok))
""",
    )
    assert "MATCH True" in out


def test_distributed_trueknn_exact_8dev():
    out = run_sub(
        """
import jax, numpy as np
from jax.sharding import Mesh
from repro.core.distributed import distributed_trueknn
from repro.core.brute import brute_knn
from repro.core.datasets import make_dataset

devs = np.array(jax.devices()).reshape(2, 4)
mesh = Mesh(devs, ("data", "model"))
pts = make_dataset("porto", 1024, seed=3)
d, idx, rounds, n_tests = distributed_trueknn(pts, 4, mesh)
bd, bi, _ = brute_knn(pts, 4)
ok = np.allclose(np.sort(d,1), np.sort(np.asarray(bd),1), rtol=1e-3, atol=1e-5)
counted = n_tests >= 1024 * 1024  # at least one full dense pass was metered
print("MATCH", bool(ok and counted), "rounds", rounds, "tests", n_tests)
""",
    )
    assert "MATCH True" in out


def test_distributed_grid_trueknn_exact_and_pruned_8dev():
    """Sharded-grid TrueKNN (per-shard hash grids + hypercube merge): exact
    vs brute AND does a fraction of the dense engine's distance tests."""
    out = run_sub(
        """
import jax, numpy as np
from jax.sharding import Mesh
from repro.core.distributed_grid import distributed_trueknn_grid
from repro.core.brute import brute_knn
from repro.core.datasets import make_dataset

devs = np.array(jax.devices()).reshape(2, 4)
mesh = Mesh(devs, ("data", "model"))
pts = make_dataset("porto", 1030, seed=3)   # non-divisible N on purpose
d, idx, stats = distributed_trueknn_grid(pts, 4, mesh)
bd, bi, _ = brute_knn(pts, 4)
ok = np.allclose(np.sort(d,1), np.sort(np.asarray(bd),1), rtol=1e-4, atol=1e-6)
pruned = stats["total_tests"] < 1030*1030 / 5
print("MATCH", bool(ok and pruned), stats["total_tests"])
""",
    )
    assert "MATCH True" in out


def test_pjit_train_step_multi_device_runs():
    """A real sharded train step executes on an 8-device mesh and the loss
    matches the single-device value (SPMD correctness end-to-end)."""
    out = run_sub(
        """
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.configs import get_config, smoke_config
from repro.models import init_params
from repro.optim import adamw_init
from repro.train import TrainConfig, make_train_step
from repro.parallel.sharding import batch_shardings, param_shardings, replicated

devs = np.array(jax.devices()).reshape(2, 4)
mesh = Mesh(devs, ("data", "model"))
cfg = smoke_config(get_config("qwen3-0.6b"))
tcfg = TrainConfig()
params = init_params(jax.random.PRNGKey(0), cfg)
opt = adamw_init(params)
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)
batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}
step = make_train_step(cfg, tcfg)

# single device reference
_, _, m_ref = jax.jit(step)(params, opt, jnp.int32(0), batch)

p_sh = param_shardings(params, cfg, mesh)
o_sh = param_shardings(opt, cfg, mesh, role="opt")
b_sh = batch_shardings(batch, cfg, mesh)
fn = jax.jit(step, in_shardings=(p_sh, o_sh, replicated(mesh), b_sh),
             out_shardings=(p_sh, o_sh, None))
with mesh:
    p2 = jax.device_put(params, p_sh)
    o2 = jax.device_put(opt, o_sh)
    b2 = jax.tree.map(lambda x, s: jax.device_put(x, s), batch, b_sh)
    _, _, m = fn(p2, o2, jnp.int32(0), b2)
print("LOSS", float(m["loss"]), float(m_ref["loss"]))
ok = abs(float(m["loss"]) - float(m_ref["loss"])) < 1e-3
print("MATCH", bool(ok))
""",
    )
    assert "MATCH True" in out


def test_compressed_psum_shard_map_8dev():
    """int8 compressed all-reduce over the data axis approximates the exact
    mean (wire format check for the grad-compression path)."""
    out = run_sub(
        """
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

devs = np.array(jax.devices())
mesh = Mesh(devs, ("data",))

def compressed_mean(x):
    # shared scale from the global max (one scalar psum), then int8 psum:
    # the wire moves 1/4 the bytes of an f32 all-reduce
    gmax = jax.lax.pmax(jnp.max(jnp.abs(x)), "data")
    scale = jnp.maximum(gmax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    qsum = jax.lax.psum(q.astype(jnp.int32), "data")
    return qsum.astype(jnp.float32) * scale / 8.0

x = np.random.default_rng(0).normal(size=(8, 64)).astype(np.float32)
fn = jax.jit(jax.shard_map(compressed_mean, mesh=mesh,
                           in_specs=P("data", None), out_specs=P(None, None),
                           check_vma=False))
got = np.asarray(fn(x)).reshape(-1)
want = x.mean(0)
err = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
print("RELERR", float(err))
print("MATCH", bool(err < 0.05))
""",
    )
    assert "MATCH True" in out
