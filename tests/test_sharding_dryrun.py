"""Sharding translation, small-mesh lowering, roofline HLO analysis.

Multi-device pieces run in a subprocess (device count must be set before
jax initializes; the main test process keeps 1 device per the assignment).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.distributed.sharding import logical_to_physical
from repro.launch.roofline import Roofline, analyze_hlo, _shape_bytes

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_logical_to_physical():
    from jax.sharding import PartitionSpec as P

    assert logical_to_physical(("dp", "tp"), False) == P("data", "model")
    assert logical_to_physical(("dp", None), True) == P(("pod", "data"), None)
    assert logical_to_physical((("dp", "tp"), None), False) == P(("data", "model"), None)
    assert logical_to_physical((None,), True) == P(None)


def test_shape_bytes():
    assert _shape_bytes("bf16[2048,4096]{1,0}") == 2048 * 4096 * 2
    assert _shape_bytes("f32[8]") == 32
    assert _shape_bytes("pred[2,2]") == 4


def test_roofline_terms_and_bottleneck():
    r = Roofline(
        flops=1e14, hbm_bytes=1e12, collective_bytes=1e11,
        model_flops=2e16, n_chips=256, device_kind="TPU v5 lite",
    )
    assert r.t_compute == pytest.approx(1e14 / 197e12)
    assert r.t_memory == pytest.approx(1e12 / 819e9)
    assert r.t_collective == pytest.approx(1e11 / 50e9)
    assert r.bottleneck == "collective"
    assert 0 < r.roofline_fraction < 1


def test_roofline_unknown_device_is_an_error():
    from repro.launch.roofline import UnknownDevice

    r = Roofline(
        flops=1e14, hbm_bytes=1e12, collective_bytes=1e11,
        model_flops=2e16, n_chips=1, device_kind="cpu",
    )
    assert r.flops == 1e14  # counts stand; no time or share does
    for term in ("t_compute", "t_memory", "t_collective", "roofline_fraction"):
        with pytest.raises(UnknownDevice, match="cpu"):
            getattr(r, term)


_SUBPROC = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_arch, SHAPES
    from repro.models import build_model
    from repro.distributed.sharding import mesh_context, logical_to_physical
    from repro.train import AdamW, AdamWConfig, make_train_step
    from repro.launch.mesh import make_debug_mesh, dp_total
    from repro.launch.roofline import analyze_hlo

    mesh = make_debug_mesh(2, 4)
    cfg = get_arch("llama3-8b").with_reduced()
    model = build_model(cfg)
    opt = AdamW(AdamWConfig(zero1=True))

    def shard(specs):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, logical_to_physical(s, False)), specs,
            is_leaf=lambda s: isinstance(s, tuple) and all(
                x is None or isinstance(x, (str, tuple)) for x in s))

    inputs = {"tokens": jax.ShapeDtypeStruct((8, 33), jnp.int32)}
    with mesh_context(mesh, False):
        step = make_train_step(model, opt)
        jf = jax.jit(step, in_shardings=(
            shard(model.param_specs()),
            shard(opt.state_specs(model.param_defs(), dp_total(mesh))),
            shard({"tokens": ("dp", None)})), donate_argnums=(0, 1))
        lowered = jf.lower(model.abstract_params(),
                           opt.abstract_state(model.abstract_params()), inputs)
        compiled = lowered.compile()
    an = analyze_hlo(compiled.as_text())
    print(json.dumps({
        "flops": an.flops,
        "collective_total": an.total_collective_bytes,
        "n_while": an.n_while,
        "has_allreduce": an.collective_bytes["all-reduce"] > 0,
    }))
    """
)


def test_small_mesh_lowering_and_hlo_analysis():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROC], capture_output=True, text=True,
        env=env, timeout=520,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["flops"] > 0
    assert rec["has_allreduce"]  # grads all-reduced over data axis
    assert rec["n_while"] >= 1   # layer scan present
