"""Multi-device behaviour: shard_map distributed search and sharded train
steps run on 8 faked host devices in a subprocess (the main test process
keeps 1 device, per dryrun.py's isolation rule)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_subprocess(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=560,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_distributed_search_8way_matches_single():
    """8-shard shard_map fan-out == host-merged per-shard results."""
    res = _run_subprocess(textwrap.dedent("""
        import json
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import GraphConfig, DiskANNIndex
        from repro.core import recall as rec
        from repro.partition.fanout import distributed_search_fn

        rng = np.random.RandomState(0)
        P, N_per, D = 8, 250, 16
        centers = rng.randn(12, D).astype(np.float32)
        shards, all_data, all_docs = [], [], []
        for p in range(P):
            data = (centers[rng.randint(0, 12, N_per)]
                    + 0.15 * rng.randn(N_per, D)).astype(np.float32)
            cfg = GraphConfig(capacity=N_per, R=12, M=8, L_build=32, L_search=32,
                              bootstrap_sample=64, refine_sample=10**9,
                              batch_size=50)
            idx = DiskANNIndex(cfg, D, seed=p)
            docs = list(range(p * N_per, (p + 1) * N_per))
            idx.insert(docs, data)
            shards.append(idx)
            all_data.append(data)
            all_docs.extend(docs)
        full = np.concatenate(all_data)

        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh((8,), ("data",))
        fn = distributed_search_fn(mesh, L=32, k=10)
        stack = lambda f: jnp.stack([f(s) for s in shards])
        args = (
            stack(lambda s: jnp.asarray(s.pv.neighbors)),
            stack(lambda s: jnp.asarray(s.pv.codes)),
            stack(lambda s: jnp.asarray(s.pv.versions)),
            stack(lambda s: jnp.asarray(s.pv.live)),
            stack(lambda s: jnp.asarray(s.pv.vectors)),
            stack(lambda s: jnp.asarray(s.slot_to_doc)),
            jnp.asarray([s.medoid for s in shards], jnp.int32),
            stack(lambda s: s.schemas[0].codebooks),
            jnp.asarray(full[rng.choice(len(full), 8)] + 0.02),
        )
        ids, dists = fn(*args)
        q = np.asarray(args[-1])
        gt = rec.ground_truth(q, full, np.ones(len(full), bool), 10)
        r = rec.recall_at_k(np.asarray(ids), gt, 10)
        print(json.dumps({"recall": r,
                          "n_devices": len(jax.devices())}))
    """))
    assert res["n_devices"] == 8
    assert res["recall"] >= 0.7, res


def test_sharded_train_step_8way_matches_single_device():
    """The pjit train step gives the same loss on a (2,4) mesh as on (1,1)."""
    res = _run_subprocess(textwrap.dedent("""
        import json
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_host_mesh
        from repro.configs import get_smoke_config
        from repro.configs.shapes import ShapeSpec, input_specs
        from repro.models import steps as steps_mod
        from repro.train.optimizer import OptConfig

        cfg = get_smoke_config("qwen3-14b")
        spec = ShapeSpec("t", 32, 4, "train")
        shapes = input_specs(cfg, spec)
        rng = np.random.RandomState(0)
        batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 32)),
                                       jnp.int32)}
        losses = {}
        for ms, ax in (((1, 1), ("data", "model")), ((2, 4), ("data", "model"))):
            mesh = make_host_mesh(ms, ax)
            b = steps_mod.make_train_step(cfg, mesh, shapes,
                                          OptConfig(lr=1e-3, total_steps=10))
            st = b.init()
            st, m = b.fn(st, batch)
            losses[str(ms)] = float(m["loss"])
        print(json.dumps(losses))
    """))
    a, b = res["(1, 1)"], res["(2, 4)"]
    assert abs(a - b) / a < 2e-2, res


def test_decode_step_sharded_cache():
    """Decode with a sequence-sharded KV cache matches unsharded math."""
    res = _run_subprocess(textwrap.dedent("""
        import json
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.models import model as M
        from repro.models import steps as steps_mod

        cfg = get_smoke_config("starcoder2-15b")
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(0)
        batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 16)), jnp.int32)}

        cache = M.init_cache(cfg, 8, 2048, dtype=jnp.float32)
        pl, cache = M.prefill(params, cfg, batch, cache)
        tok = jnp.argmax(pl[:, 0], -1).astype(jnp.int32)[:, None]
        ref_logits, _ = M.decode_step(params, cfg, tok, cache, jnp.int32(16))

        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh((2, 4), ("data", "model"))
        bundle = steps_mod.make_decode_step(cfg, mesh, batch=8, s_max=2048,
                                            cache_dtype=jnp.float32)
        params_sh = jax.device_put(params, bundle.arg_shardings[0])
        cache_sh = jax.device_put(cache, bundle.arg_shardings[1])
        out, _ = bundle.fn(params_sh, cache_sh, jax.device_put(tok, bundle.arg_shardings[2]), jnp.int32(16))
        err = float(jnp.abs(out - ref_logits).max())
        print(json.dumps({"err": err}))
    """))
    assert res["err"] < 1e-2, res
