"""Distribution machinery tests on a small host-platform mesh.

The main pytest session must keep seeing ONE device (smoke tests, benches),
so anything needing multiple devices runs in a subprocess that sets
XLA_FLAGS=--xla_force_host_platform_device_count before importing jax —
the same pattern as the production dry-run, scaled down to a (2, 4) mesh.
"""

import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import get_smoke_config
    from repro.distributed.param_sharding import build_param_specs, spec_tree_to_shardings
    from repro.distributed.sharding import use_rules
    from repro.models.model import build
    from repro.optim.adamw import AdamWConfig
    from repro.train.trainer import TrainConfig, init_train_state, make_train_step

    assert jax.device_count() == 8
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)

    # rules sized for the small mesh (model axis = 4)
    rules = {
        "batch": "data", "seq": None, "seq_sp": None, "d_model": None,
        "heads_flat": "model", "kv_heads": None, "d_ff": "model",
        "vocab": "model", "experts": None, "dispatch_groups": "data",
        "d_inner": "model", "state": None,
    }

    for arch in ("llama3_2_1b", "granite_moe_3b_a800m", "falcon_mamba_7b"):
        cfg = dataclasses.replace(get_smoke_config(arch), moe_dispatch_groups=2)
        model = build(cfg)
        tc = TrainConfig(optimizer=AdamWConfig(lr=1e-3), microbatches=2)
        state = init_train_state(model, jax.random.PRNGKey(0), tc)
        specs = build_param_specs(jax.eval_shape(lambda: state["params"]), model_size=4)
        shardings = {
            "params": spec_tree_to_shardings(specs, mesh),
            "opt": {
                "m": spec_tree_to_shardings(build_param_specs(
                    jax.eval_shape(lambda: state["opt"]["m"]), 4), mesh),
                "v": spec_tree_to_shardings(build_param_specs(
                    jax.eval_shape(lambda: state["opt"]["v"]), 4), mesh),
                "step": NamedSharding(mesh, P()),
            },
        }
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0, cfg.vocab_size)}
        bspec = {"tokens": NamedSharding(mesh, P("data", None))}
        with mesh:
            with use_rules(rules):
                step = jax.jit(make_train_step(model, tc),
                               in_shardings=(shardings, bspec))
                state_s = jax.device_put(state, shardings)
                batch_s = jax.device_put(batch, bspec)
                new_state, metrics = step(state_s, batch_s)
        loss = float(metrics["loss"])
        assert loss == loss and loss > 0, (arch, loss)  # finite
        # sharded result must equal the single-device result numerically
        step1 = jax.jit(make_train_step(model, tc))
        _, metrics1 = step1(state, batch)
        assert abs(loss - float(metrics1["loss"])) < 1e-3, (arch, loss, float(metrics1["loss"]))
        print(f"{arch}: sharded loss {loss:.4f} == unsharded {float(metrics1['loss']):.4f}")
    print("DISTRIBUTION_OK")
    """
)


@pytest.mark.slow
def test_sharded_train_step_matches_unsharded():
    """Full train_step on a (2,4) mesh: compiles, runs, matches 1-device loss."""
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": "src"},
    )
    assert "DISTRIBUTION_OK" in out.stdout, out.stdout[-2000:] + out.stderr[-3000:]
