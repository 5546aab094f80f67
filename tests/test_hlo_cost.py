"""The scan-aware HLO cost walker: exactness on known programs.

This is the §Roofline measurement instrument, so it gets its own tests:
XLA's cost_analysis counts while bodies once (demonstrated here), the
walker multiplies by trip count.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_cost import analyze_hlo

N = 256


def _compile(fn, *sds):
    return jax.jit(fn).lower(*sds).compile()


def test_plain_matmul_exact():
    c = _compile(lambda a, b: a @ b,
                 jax.ShapeDtypeStruct((N, N), jnp.float32),
                 jax.ShapeDtypeStruct((N, N), jnp.float32))
    got = analyze_hlo(c.as_text())
    assert got.flops == pytest.approx(2 * N ** 3, rel=0.01)
    assert got.traffic_bytes == pytest.approx(3 * N * N * 4, rel=0.05)


def test_scan_multiplies_trip_count():
    def g(a, bs):
        def body(x, b):
            return x @ b, ()
        out, _ = jax.lax.scan(body, a, bs)
        return out

    c = _compile(g, jax.ShapeDtypeStruct((N, N), jnp.float32),
                 jax.ShapeDtypeStruct((10, N, N), jnp.float32))
    got = analyze_hlo(c.as_text())
    assert got.flops == pytest.approx(20 * N ** 3, rel=0.02)
    assert 10 in got.while_trips.values()
    # ... and XLA's own cost_analysis does NOT (the reason this module exists)
    xla = c.cost_analysis().get("flops", 0.0)
    assert xla < 0.2 * got.flops


def test_nested_scans_multiply():
    def h(a, bs):
        def outer(x, b5):
            def inner(y, b):
                return y @ b, ()
            y, _ = jax.lax.scan(inner, x, b5)
            return y, ()
        out, _ = jax.lax.scan(outer, a, bs)
        return out

    c = _compile(h, jax.ShapeDtypeStruct((N, N), jnp.float32),
                 jax.ShapeDtypeStruct((5, 4, N, N), jnp.float32))
    got = analyze_hlo(c.as_text())
    assert got.flops == pytest.approx(40 * N ** 3, rel=0.02)


def test_grad_counts_forward_and_backward():
    def loss(w, x):
        return jnp.sum(jnp.tanh(x @ w) ** 2)

    c = _compile(jax.grad(loss),
                 jax.ShapeDtypeStruct((N, N), jnp.float32),
                 jax.ShapeDtypeStruct((N, N), jnp.float32))
    got = analyze_hlo(c.as_text())
    # fwd x@w (2N^3) + bwd dW = x^T @ dY (2N^3); dL/dx is DCE'd since we
    # only differentiate w.r.t. w -> ~4N^3 + elementwise
    assert 3.9 * N ** 3 < got.flops < 4.6 * N ** 3


def test_elementwise_counted_once_per_element():
    c = _compile(lambda a: jnp.tanh(a) + a * a,
                 jax.ShapeDtypeStruct((N, N), jnp.float32))
    got = analyze_hlo(c.as_text())
    # 3 elementwise ops x N^2 elems, allow fusion slack either way
    assert N ** 2 <= got.flops <= 8 * N ** 2


def test_comment_in_tuple_types_handled():
    """Long tuple types carry /*index=5*/ comments that contain '=' — the
    regression that silently dropped every while op (see git history)."""
    def g(carry, xs):
        def body(c, x):
            a, b, d, e, f, h = c
            return (a @ x, b + 1, d * 2, e - 1, f + a[0, 0], h), ()
        out, _ = jax.lax.scan(body, carry, xs)
        return out

    carry = tuple(jax.ShapeDtypeStruct((N, N), jnp.float32) for _ in range(1)) + \
        tuple(jax.ShapeDtypeStruct((), jnp.float32) for _ in range(5))
    c = _compile(g, carry, jax.ShapeDtypeStruct((7, N, N), jnp.float32))
    got = analyze_hlo(c.as_text())
    assert got.flops > 0.95 * 14 * N ** 3
    assert 7 in got.while_trips.values()
