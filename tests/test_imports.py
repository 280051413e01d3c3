"""Every repro.* module must import on the installed JAX (an import
error there fails the whole suite's collection).  The sweep runs in a
fresh interpreter, so that each module is shown to import without what
other tests of this process imported first.
"""
import os
import subprocess
import sys

import numpy as np

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_every_repro_module_imports():
    code = (
        "import importlib, pkgutil, repro\n"
        "mods = [m.name for m in pkgutil.walk_packages(repro.__path__,"
        " 'repro.')]\n"
        "for m in mods:\n"
        "    importlib.import_module(m)\n"
        "print(len(mods))\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(_SRC) + os.pathsep \
        + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, env=env)
    assert proc.returncode == 0, proc.stderr
    assert int(proc.stdout.strip()) >= 25


def test_grouped_gemm_grads_match_dense_reference():
    """The backward pass must agree with a dense per-group reference on
    both gradients."""
    import jax
    import jax.numpy as jnp
    from repro.models.grouped_gemm import grouped_gemm

    rng = np.random.default_rng(0)
    gs = np.array([3, 0, 5, 4], np.int32)
    m, k, n, g = int(gs.sum()), 6, 5, len(gs)
    lhs = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(g, k, n)), jnp.float32)
    group_sizes = jnp.asarray(gs)

    def dense_ref(lhs, rhs):
        gid = np.repeat(np.arange(g), gs)
        onehot = jnp.asarray(np.eye(g, dtype=np.float32)[gid])
        return jnp.einsum("mk,mg,gkn->mn", lhs, onehot, rhs)

    y = grouped_gemm(lhs, rhs, group_sizes)
    np.testing.assert_allclose(y, dense_ref(lhs, rhs), atol=1e-5)

    loss = lambda f: lambda a, b: jnp.sum(jnp.sin(f(a, b)))
    gl, gr = jax.grad(loss(lambda a, b: grouped_gemm(a, b, group_sizes)),
                      argnums=(0, 1))(lhs, rhs)
    rl, rr = jax.grad(loss(dense_ref), argnums=(0, 1))(lhs, rhs)
    np.testing.assert_allclose(gl, rl, atol=1e-5)
    np.testing.assert_allclose(gr, rr, atol=1e-5)
