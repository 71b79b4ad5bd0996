"""Infrastructure units: HLO analysis (trip counts), ShardPlan, optimizer
state specs, serving engine bucketing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (
    MULTI_POD_PLAN,
    SINGLE_POD_PLAN,
    ShardPlan,
)
from repro.launch.hlo_analysis import analyze_hlo, peak_liveness
from repro.train import optim


def test_analyze_hlo_weights_scan_bodies_by_trip_count():
    def scanned(w, x):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        y, _ = jax.lax.scan(body, x, w)
        return y

    c = jax.jit(scanned).lower(
        jax.ShapeDtypeStruct((32, 64, 64), jnp.float32),
        jax.ShapeDtypeStruct((8, 64), jnp.float32),
    ).compile()
    a = analyze_hlo(c.as_text())
    # exact matmul flops: 32 iterations x 2*8*64*64
    want = 32 * 2 * 8 * 64 * 64
    assert abs(a["matmul_flops"] - want) / want < 0.01
    assert any(abs(v - 32) < 0.5
               for v in a["while_trip_multipliers"].values())


def test_analyze_hlo_counts_collectives():
    import os
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("d",))
    def f(x, w):
        return (x @ w).sum()
    with mesh:
        c = jax.jit(f, in_shardings=(NamedSharding(mesh, P(None, "d")),
                                     NamedSharding(mesh, P("d", None))),
                    out_shardings=NamedSharding(mesh, P())).lower(
            jax.ShapeDtypeStruct((64, 64), jnp.float32),
            jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    a = analyze_hlo(c.as_text())
    print("COLL", a["collective_bytes"]["total"] > 0)
    """)
    from conftest import REPO, subprocess_env

    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env=subprocess_env(), cwd=REPO)
    assert r.returncode == 0, r.stderr[-1500:]
    assert "COLL True" in r.stdout


def test_peak_liveness_returns_buffers():
    def f(x):
        a = jnp.tanh(x @ x.T)
        b = a @ a
        return b.sum()

    c = jax.jit(f).lower(
        jax.ShapeDtypeStruct((256, 256), jnp.float32)).compile()
    pl = peak_liveness(c.as_text())
    peaks = [v["peak_bytes"] for v in pl.values()]
    assert max(peaks) >= 256 * 256 * 4


def test_shard_plan_roles_resolve():
    p = SINGLE_POD_PLAN
    assert p.p("dp", None) == P(("data",), None)
    assert p.p("fsdp", "tp") == P(("data",), ("model",))
    assert p.p(("dp", "tp")) == P(("data", "model"))
    m = MULTI_POD_PLAN
    assert m.p("dp") == P(("pod", "data"))
    assert m.resolve("ep") == ("data", "model")
    # empty plan -> fully replicated
    assert ShardPlan().p("dp", "tp") == P(None, None)


def test_div_p_drops_indivisible_dims():
    # mesh needs real devices; emulate sizes via a fake plan with mesh=None
    # -> size 1 divides everything, roles keep
    p = ShardPlan(dp=("data",), fsdp=("data",), tp=("model",))
    # without a mesh sizes are 1 -> everything "divides"
    assert p.div_p((13, 512), "fsdp", "tp") == P(("data",), ("model",))


def test_state_specs_match_state_structure():
    params = {"w": jnp.zeros((256, 512)), "b": jnp.zeros((512,))}
    specs = {"w": P("data", "model"), "b": P(None)}
    shapes = jax.eval_shape(lambda: params)
    for opt in (optim.adamw(optim.constant_lr(1e-3)),
                optim.sgd(optim.constant_lr(1e-3)),
                optim.adafactor(optim.constant_lr(1e-3),
                                min_dim_factored=128)):
        state = opt.init(params)
        sspecs = optim.state_specs(opt, specs, shapes)
        # structures must match exactly (zip in jit sharding paths)
        jax.tree.map(lambda a, b: None, state, sspecs,
                     is_leaf=lambda x: isinstance(x, P))


def test_adafactor_factored_spec_shapes():
    opt = optim.adafactor(optim.constant_lr(1e-2), min_dim_factored=128)
    spec = opt.state_spec_fn(P("data", "model"), (256, 512))
    assert spec == {"vr": P("data"), "vc": P("model")}
    spec = opt.state_spec_fn(P(None), (64,))
    assert spec == {"v": P(None)}


def test_doc_links_resolve():
    """Every intra-repo markdown link must resolve (the CI docs job runs
    the same checker; this keeps it enforced in the tier-1 suite too)."""
    import subprocess
    import sys

    from conftest import REPO

    r = subprocess.run(
        [sys.executable, "tools/check_doc_links.py"],
        capture_output=True, text=True, cwd=REPO, timeout=60,
    )
    assert r.returncode == 0, r.stdout + r.stderr
