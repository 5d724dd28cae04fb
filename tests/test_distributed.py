"""Distribution: sharding-rule unit tests + an 8-host-device integration run
(subprocess, because XLA device count must be set before jax initializes)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from jax.sharding import PartitionSpec as PS


class TestSpecFor:
    def _mesh(self, shape=(2, 4), axes=("data", "model")):
        # host platform has 1 device in this process: build an abstract mesh
        from jax.sharding import AbstractMesh
        return AbstractMesh(shape, axes)

    def test_dense_weight(self):
        from repro.distributed.sharding import spec_for
        mesh = self._mesh()
        assert spec_for((64, 128), ("embed", "ffn"), mesh) == PS("data", "model")

    def test_heads_not_divisible_falls_back_to_embed(self):
        from repro.distributed.sharding import spec_for
        mesh = self._mesh((2, 4))
        # 3 heads unshardable on 4-wide model axis -> model stacks on embed
        spec = spec_for((64, 3, 16), ("embed", "heads", "hdim"), mesh)
        assert spec == PS(("data", "model"), None, None)

    def test_kv_cache_seq_fallback(self):
        from repro.distributed.sharding import spec_for
        mesh = self._mesh((2, 4))
        # kv=2 unshardable on 4-wide model -> model lands on seq
        spec = spec_for((8, 2, 64, 2, 16),
                        ("layers", "batch", "seq", "kv", "hdim"), mesh)
        assert spec == PS(None, "data", "model", None, None)

    def test_batch_one_replicated(self):
        from repro.distributed.sharding import batch_spec
        mesh = self._mesh((2, 4))
        assert batch_spec(mesh, 2, batch_dim=1) == PS(None, None)
        assert batch_spec(mesh, 2, batch_dim=6) == PS("data", None)

    def test_expert_weights(self):
        from repro.distributed.sharding import spec_for
        mesh = self._mesh()
        spec = spec_for((8, 64, 96), ("exp", "embed", "ffn"), mesh)
        assert spec == PS("model", "data", None)

    def test_multi_pod_batch(self):
        from repro.distributed.sharding import batch_spec
        mesh = self._mesh((2, 2, 2), ("pod", "data", "model"))
        assert batch_spec(mesh, 2, batch_dim=8) == PS(("pod", "data"), None)


_SUBPROCESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax, jax.numpy as jnp, numpy as np
    sys.path.insert(0, {src!r})
    from repro.models import Model, ModelConfig, AttnCfg, MoECfg, SSMCfg
    from repro.launch.mesh import make_mesh
    from repro.train.train_step import build_train_step
    from repro.train import optimizer as opt

    out = {{}}
    for name, cfg, mesh_shape, axes in [
        ("dense_2x4", ModelConfig("d", "dense", 2, 64, 128, 256,
                                  attn=AttnCfg(4, 2, 16), remat=True),
         (2, 4), ("data", "model")),
        ("moe_2x4", ModelConfig("m", "moe", 2, 64, 128, 256,
                                attn=AttnCfg(4, 2, 16),
                                moe=MoECfg(8, 2, 96, shared_ff=64)),
         (2, 4), ("data", "model")),
        ("ssm_pod", ModelConfig("s", "ssm", 2, 64, 0, 256,
                                ssm=SSMCfg(d_state=16, headdim=16, chunk=8)),
         (2, 2, 2), ("pod", "data", "model")),
    ]:
        mesh = make_mesh(mesh_shape, axes)
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        ostate = opt.init_opt_state(params)
        _, jit_step, shards = build_train_step(
            model, mesh, opt.OptConfig(lr=1e-3, warmup_steps=2,
                                       total_steps=50),
            microbatches=2)
        B, S = 8, 32
        rng = np.random.default_rng(0)
        batch = {{"tokens": jnp.asarray(rng.integers(0, 256, (B, S)),
                                        jnp.int32)}}
        f = jit_step({{"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}})
        params = jax.device_put(params, shards["params"])
        ostate = jax.device_put(ostate, shards["opt"])
        losses = []
        for _ in range(4):
            params, ostate, m = f(params, ostate, batch)
            losses.append(float(m["loss"]))
        out[name] = losses
    print("RESULT " + json.dumps(out))
""")


@pytest.mark.slow
def test_8device_train_all_parallelism_modes(tmp_path):
    """DP×TP (+EP via shard_map, +pod axis) on 8 host devices: losses finite
    and decreasing for dense, MoE and SSM families."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = _SUBPROCESS_SCRIPT.format(src=os.path.abspath(src))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    res = json.loads(line[len("RESULT "):])
    for name, losses in res.items():
        assert all(np.isfinite(losses)), (name, losses)
        assert losses[-1] < losses[0], (name, losses)
