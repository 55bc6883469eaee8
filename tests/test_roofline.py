"""The HLO cost walker (launch/roofline.py) against known ground truths.

The whole §Roofline analysis rests on this parser, so it gets its own
oracle tests: exact dot FLOPs, while-loop trip multiplication (XLA's own
cost_analysis counts loop bodies once — verified here), and collective
byte extraction in a multi-device subprocess.  The peaks table behind
roofline fractions is keyed by exact device kind and refuses to guess.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.launch.roofline import analyze_hlo, device_peaks

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dot_flops_exact():
    f = jax.jit(lambda a, b: a @ b)
    a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 64), jnp.float32)
    hlo = f.lower(a, b).compile().as_text()
    cost = analyze_hlo(hlo)
    assert cost.flops == 2 * 128 * 256 * 64


def test_scan_multiplies_trip_count():
    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        return jax.lax.scan(body, x, w)[0]

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((10, 64, 64), jnp.float32)
    hlo = jax.jit(f).lower(x, w).compile().as_text()
    cost = analyze_hlo(hlo)
    one = 2 * 64 * 64 * 64
    assert cost.flops == 10 * one, (cost.flops, one)
    # (XLA's own cost_analysis is inconsistent here: it counted the body
    # once for a 512x512 scan but multiplies small/unrolled loops — which
    # is exactly why the roofline does its own trip-aware accounting.)


def test_nested_scan_multiplies():
    def f(x, w):
        def outer(c, wi):
            def inner(cc, _):
                return jnp.tanh(cc @ wi), None
            return jax.lax.scan(inner, c, None, length=3)[0], None
        return jax.lax.scan(outer, x, w)[0]

    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    w = jax.ShapeDtypeStruct((4, 32, 32), jnp.float32)
    hlo = jax.jit(f).lower(x, w).compile().as_text()
    cost = analyze_hlo(hlo)
    assert cost.flops == 4 * 3 * 2 * 32 ** 3, cost.flops


def test_batched_dot_flops():
    f = jax.jit(lambda a, b: jnp.einsum("bij,bjk->bik", a, b))
    a = jax.ShapeDtypeStruct((8, 16, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((8, 32, 24), jnp.float32)
    hlo = f.lower(a, b).compile().as_text()
    assert analyze_hlo(hlo).flops == 2 * 8 * 16 * 32 * 24


def test_collective_bytes_subprocess():
    body = """
    import os, jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.roofline import analyze_hlo
    mesh = jax.make_mesh((8,), ("data",))

    def f(x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P()))       # forces an all-gather

    x = jax.ShapeDtypeStruct((1024, 256), jnp.float32)
    sh = NamedSharding(mesh, P("data", None))
    # out_shardings must pin the replicated layout: recent XLA propagates
    # the constraint sharding to the output and elides the all-gather
    # entirely when the output placement is left free.
    hlo = jax.jit(f, in_shardings=sh,
                  out_shardings=NamedSharding(mesh, P())
                  ).lower(x).compile().as_text()
    cost = analyze_hlo(hlo)
    total = sum(cost.coll_by_kind.values())
    expect = 1024 * 256 * 4                    # gathered result bytes
    assert "all-gather" in cost.coll_by_kind, cost.coll_by_kind
    assert abs(total - expect) / expect < 0.01, (total, expect)
    print("collectives OK", cost.coll_by_kind)
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr


def test_bytes_hbm_reasonable_for_matmul():
    f = jax.jit(lambda a, b: a @ b)
    a = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    hlo = f.lower(a, a).compile().as_text()
    cost = analyze_hlo(hlo)
    ideal = 3 * 512 * 512 * 4       # read a, b; write c
    assert ideal <= cost.bytes_hbm <= 3 * ideal, cost.bytes_hbm


def test_v5e_peaks_are_the_published_ones():
    assert device_peaks("TPU v5 lite") == (197e12, 819e9)


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v6 lite", "tpu",
                                  "NVIDIA H100", "weird-accel"])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        device_peaks(kind)
