"""Multi-device behaviour on 8 fake host devices (subprocess-isolated)."""
import pytest

from conftest import run_with_devices


def test_ring_spgemm_8dev():
    run_with_devices("""
import warnings; warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from repro.core import ell_rows_from_dense, ell_cols_from_dense
from repro.core.distributed import ring_spgemm
rng = np.random.default_rng(1)
n = 32
A = ((rng.random((n,n)) < 0.25) * rng.standard_normal((n,n))).astype(np.float32)
B = ((rng.random((n,n)) < 0.25) * rng.standard_normal((n,n))).astype(np.float32)
a = ell_rows_from_dense(jnp.array(A), 16)
b = ell_cols_from_dense(jnp.array(B), 16)
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("ring",))
C = ring_spgemm(a, b, mesh, "ring")
np.testing.assert_allclose(np.asarray(C), A@B, atol=1e-4)
print("OK")
""")


def test_ring_all_to_all_matches_transpose():
    run_with_devices("""
import warnings; warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.distributed import ring_all_to_all
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("ring",))
x = jnp.arange(8*8*4, dtype=jnp.float32).reshape(8, 8, 4)
out = jax.shard_map(lambda xs: ring_all_to_all(xs[0], "ring")[None],
                    mesh=mesh, in_specs=P("ring"), out_specs=P("ring"))(x)
np.testing.assert_allclose(np.asarray(out), np.asarray(jnp.swapaxes(x, 0, 1)))
print("OK")
""")


def test_sharded_train_step_runs_dp_tp():
    """Real train step on a 4×2 (data×model) mesh with a reduced config."""
    run_with_devices("""
import warnings; warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models import build_model
from repro.launch.steps import make_train_step, abstract_train_args
from repro.optim import AdamWConfig, adamw_init
from repro.parallel.sharding import sharding_rules
import dataclasses
cfg = dataclasses.replace(get_config("granite-moe-3b-a800m").reduced(),
                          d_model=64, vocab=256)
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
model = build_model(cfg)
with sharding_rules(mesh), mesh:
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    step = jax.jit(make_train_step(model, AdamWConfig()), donate_argnums=(0,1))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)}
    p2, o2, m = step(params, opt, batch)
    assert np.isfinite(float(m["loss"]))
print("OK")
""")


def test_moe_expert_parallel_equivalence():
    """MoE loss identical on 1 device vs expert-sharded 8 devices."""
    run_with_devices("""
import warnings; warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models import build_model
from repro.parallel.sharding import sharding_rules
cfg = get_config("granite-moe-3b-a800m").reduced()
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab)}
l1 = float(model.loss(params, batch))
from repro.launch.mesh import make_mesh
mesh = make_mesh((1, 8), ("data", "model"))
with sharding_rules(mesh), mesh:
    l8 = float(jax.jit(model.loss)(params, batch))
np.testing.assert_allclose(l1, l8, rtol=2e-2)
print("OK")
""")


def test_moe_sort_dispatch_sharded_equivalence():
    """SPLIM sort dispatch (manual shard_map) matches single-device loss."""
    run_with_devices("""
import warnings; warnings.filterwarnings("ignore")
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models import build_model
from repro.parallel.sharding import sharding_rules
base = get_config("deepseek-v2-lite-16b").reduced()
cfg = dataclasses.replace(base, moe=dataclasses.replace(
    base.moe, dispatch="sort", capacity_factor=4.0))
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab)}
l1 = float(model.loss(params, batch))
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
with sharding_rules(mesh), mesh:
    l8 = float(jax.jit(model.loss)(params, batch))
np.testing.assert_allclose(l1, l8, rtol=2e-2)
print("OK")
""")


def test_compressed_psum_mean_8dev():
    run_with_devices("""
import warnings; warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.optim import compressed_psum_mean
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
g = jnp.linspace(-1, 1, 8*32).reshape(8, 32).astype(jnp.float32)
def f(gs):
    mean, err = compressed_psum_mean({"g": gs[0]}, "data")
    return mean["g"][None], err["g"][None]
mean, err = jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data"))(g)
true = np.asarray(g).mean(0)
got = np.asarray(mean)[0]
np.testing.assert_allclose(got, true, atol=0.02)
# error feedback bounded by one quantization step
assert np.abs(np.asarray(err)).max() <= np.abs(np.asarray(g)).max()/127 + 1e-6
print("OK")
""")
