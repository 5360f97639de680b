"""Validate the trip-aware HLO analyzer against unrolled references."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.launch.hlo_analysis import analyze, parse_hlo


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_scan_flops_match_unrolled():
    D, T = 256, 6
    xs = jax.ShapeDtypeStruct((32, D), jnp.float32)
    ws = jax.ShapeDtypeStruct((T, D, D), jnp.float32)

    def scanned(x, w):
        def body(x, wi):
            return jnp.dot(x, wi), None
        return jax.lax.scan(body, x, w)[0]

    def unrolled(x, w):
        for i in range(T):
            x = jnp.dot(x, w[i])
        return x

    c_scan = analyze(_compile(scanned, xs, ws).as_text())
    c_unr = analyze(_compile(unrolled, xs, ws).as_text())
    want = 2 * 32 * D * D * T
    assert c_scan.flops == want
    assert c_unr.flops == want


def test_nested_scan_multiplier():
    D, T1, T2 = 128, 3, 5
    xs = jax.ShapeDtypeStruct((8, D), jnp.float32)
    ws = jax.ShapeDtypeStruct((D, D), jnp.float32)

    def nested(x, w):
        def outer(x, _):
            def inner(x, _):
                return jnp.dot(x, w), None
            return jax.lax.scan(inner, x, None, length=T2)[0], None
        return jax.lax.scan(outer, x, None, length=T1)[0]

    cost = analyze(_compile(nested, xs, ws).as_text())
    assert cost.flops == 2 * 8 * D * D * T1 * T2


def test_xla_cost_analysis_undercounts_but_we_dont():
    """Documents the very bug this module exists for."""
    D, T = 256, 8
    xs = jax.ShapeDtypeStruct((16, D), jnp.float32)
    ws = jax.ShapeDtypeStruct((T, D, D), jnp.float32)

    def scanned(x, w):
        def body(x, wi):
            return jnp.dot(x, wi), None
        return jax.lax.scan(body, x, w)[0]

    compiled = _compile(scanned, xs, ws)
    xla_flops = compiled.cost_analysis()["flops"]
    ours = analyze(compiled.as_text()).flops
    want = 2 * 16 * D * D * T
    assert xla_flops < want / 2          # XLA counts the body once
    assert ours == want


def test_collectives_inside_scan_are_trip_multiplied():
    import os
    T, D = 4, 64
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >1 device (dryrun covers the multi-device path)")


def test_bytes_scale_with_trip_count():
    D = 128
    xs = jax.ShapeDtypeStruct((8, D), jnp.float32)
    ws = jax.ShapeDtypeStruct((D, D), jnp.float32)

    def make(T):
        def f(x, w):
            def body(x, _):
                return jnp.dot(x, w), None
            return jax.lax.scan(body, x, None, length=T)[0]
        return f

    b2 = analyze(_compile(make(2), xs, ws).as_text()).bytes_accessed
    b8 = analyze(_compile(make(8), xs, ws).as_text()).bytes_accessed
    # Per-trip traffic is 4x; entry-computation overhead (copies of the
    # loop-invariant weights etc.) dilutes the ratio at toy sizes.
    per_trip = (b8 - b2) / 6
    assert per_trip == pytest.approx(73_728, rel=0.35)  # dot in/out bytes


def test_parse_recovers_computations():
    D = 32
    xs = jax.ShapeDtypeStruct((4, D), jnp.float32)
    ws = jax.ShapeDtypeStruct((D, D), jnp.float32)
    txt = _compile(lambda x, w: jnp.dot(x, w), xs, ws).as_text()
    comps = parse_hlo(txt)
    assert any(c.is_entry for c in comps.values())
    assert any(i.op == "dot" for c in comps.values() for i in c.instrs)
