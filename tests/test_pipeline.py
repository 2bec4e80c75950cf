"""GPipe pipeline over 8 fake devices matches sequential execution."""
from conftest import run_with_devices


def test_pipeline_matches_sequential():
    run_with_devices("""
import warnings; warnings.filterwarnings("ignore")
import numpy as np, jax, jax.numpy as jnp
from repro.parallel.pipeline import pipeline_apply
n_stages, n_micro, mb, d = 8, 6, 4, 16
rng = jax.random.PRNGKey(0)
params = {"w": jax.random.normal(rng, (n_stages, d, d)) * 0.3,
          "b": jax.random.normal(jax.random.PRNGKey(1), (n_stages, d)) * 0.1}
x = jax.random.normal(jax.random.PRNGKey(2), (n_micro, mb, d))

def stage_fn(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])

# sequential reference
ref = x
for s in range(n_stages):
    ref = stage_fn({"w": params["w"][s], "b": params["b"][s]}, ref)

from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("pipe",))
out = pipeline_apply(stage_fn, params, x, mesh, axis="pipe")
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
print("OK")
""")
