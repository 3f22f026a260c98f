"""Validate the trip-count-aware HLO cost analyzer against ground truth."""

import pytest

import jax
import jax.numpy as jnp

from repro.launch import hlo_cost


def _compile(f, *specs, **jit_kw):
    return jax.jit(f, **jit_kw).lower(*specs).compile()


def test_plain_matmul_flops():
    m, k, n = 64, 128, 256
    co = _compile(lambda a, b: a @ b,
                  jax.ShapeDtypeStruct((m, k), jnp.float32),
                  jax.ShapeDtypeStruct((k, n), jnp.float32))
    cost = hlo_cost.analyze(co.as_text())
    assert cost.flops == 2 * m * k * n
    assert cost.collective_bytes == 0


def test_scan_multiplies_by_trip_count():
    layers, m, d = 7, 32, 64

    def f(ws, x):
        def body(x, w):
            return jnp.tanh(x @ w), None
        x, _ = jax.lax.scan(body, x, ws)
        return x

    co = _compile(f, jax.ShapeDtypeStruct((layers, d, d), jnp.float32),
                  jax.ShapeDtypeStruct((m, d), jnp.float32))
    cost = hlo_cost.analyze(co.as_text())
    assert cost.flops == layers * 2 * m * d * d, cost.loops
    assert any(t == layers for _, t in cost.loops)


def test_scan_matches_unrolled_xla_cost():
    """Our loop-corrected flops == XLA's own count on the unrolled version."""
    layers, m, d = 5, 16, 32

    def scanned(ws, x):
        def body(x, w):
            return x @ w, None
        x, _ = jax.lax.scan(body, x, ws)
        return x

    def unrolled(ws, x):
        for i in range(layers):
            x = x @ ws[i]
        return x

    ws = jax.ShapeDtypeStruct((layers, d, d), jnp.float32)
    xs = jax.ShapeDtypeStruct((m, d), jnp.float32)
    co_s = _compile(scanned, ws, xs)
    co_u = _compile(unrolled, ws, xs)
    ours = hlo_cost.analyze(co_s.as_text()).flops
    xla_unrolled = co_u.cost_analysis()["flops"]
    assert ours == pytest.approx(xla_unrolled, rel=0.01)


def test_nested_scans_multiply():
    inner, outer, d = 3, 4, 16

    def f(ws, x):
        def outer_body(x, w_outer):
            def inner_body(x2, _):
                return jnp.sin(x2 @ w_outer), None
            x2, _ = jax.lax.scan(inner_body, x, None, length=inner)
            return x2, None
        x, _ = jax.lax.scan(outer_body, x, ws)
        return x

    co = _compile(f, jax.ShapeDtypeStruct((outer, d, d), jnp.float32),
                  jax.ShapeDtypeStruct((8, d), jnp.float32))
    cost = hlo_cost.analyze(co.as_text())
    assert cost.flops == outer * inner * 2 * 8 * d * d


def test_grad_of_scan_counts_fwd_and_bwd():
    layers, m, d = 6, 8, 16

    def loss(ws, x):
        def body(x, w):
            return jnp.tanh(x @ w), None
        x, _ = jax.lax.scan(body, x, ws)
        return jnp.sum(x * x)

    co = _compile(jax.grad(loss), jax.ShapeDtypeStruct((layers, d, d), jnp.float32),
                  jax.ShapeDtypeStruct((m, d), jnp.float32))
    cost = hlo_cost.analyze(co.as_text())
    # fwd: 2md^2 per layer; bwd: dx (2md^2) + dw (2md^2) per layer => 3x fwd
    want = layers * 3 * 2 * m * d * d
    assert cost.flops == pytest.approx(want, rel=0.05), (cost.flops, want)


def test_collectives_inside_loops_are_multiplied():
    import subprocess, sys, os, textwrap
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import compat
        from repro.launch import hlo_cost
        mesh = compat.make_mesh((8,), ("model",))
        L, m, d = 5, 32, 64
        def f(ws, x):
            def body(x, w):
                return jnp.tanh(x @ w), None
            x, _ = jax.lax.scan(body, x, ws)
            return x
        ws = jax.ShapeDtypeStruct((L, d, d), jnp.float32)
        xs = jax.ShapeDtypeStruct((m, d), jnp.float32)
        co = jax.jit(f, in_shardings=(
            NamedSharding(mesh, P(None, None, "model")),
            NamedSharding(mesh, P(None, "model"))),
            out_shardings=NamedSharding(mesh, P(None, "model"))
        ).lower(ws, xs).compile()
        cost = hlo_cost.analyze(co.as_text())
        # per trip the sharded matmul needs at least one gather/reduce step;
        # whatever XLA chose, the total must scale with L (counted > once)
        per_loop = [t for _, t in cost.loops]
        assert L in per_loop, cost.loops
        assert cost.collective_bytes > 0
        single = cost.collective_bytes / L
        # sanity: collective bytes are a multiple of the per-trip cost
        assert abs(cost.collective_bytes - single * L) < 1e-6
        print("COLL-OK", cost.collective_bytes, cost.coll_by_class)
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "COLL-OK" in out.stdout


def test_bytes_model_counts_dots_not_elementwise():
    """Fusion-aware HBM model: matmul operands/results count; pure
    elementwise chains are treated as fused epilogues (~free)."""
    m, k, n = 64, 128, 256

    def heavy(a, b):
        return jnp.tanh(a @ b) * 2.0

    co = _compile(heavy, jax.ShapeDtypeStruct((m, k), jnp.float32),
                  jax.ShapeDtypeStruct((k, n), jnp.float32))
    cost = hlo_cost.analyze(co.as_text())
    dot_io = 4 * (m * k + k * n + m * n)
    assert cost.bytes_accessed >= dot_io
    assert cost.bytes_accessed < 4 * dot_io  # not counting every op

    def elementwise_only(x):
        def body(c, _):
            return jnp.tanh(c) * 2.0, None
        c, _ = jax.lax.scan(body, x, None, length=10)
        return c

    co2 = _compile(elementwise_only, jax.ShapeDtypeStruct((1024,), jnp.float32))
    cost2 = hlo_cost.analyze(co2.as_text())
    # only loop-state copies remain; far below the 10x read+write upper bound
    assert cost2.bytes_accessed < 10 * 2 * 4096
