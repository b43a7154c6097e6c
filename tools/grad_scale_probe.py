"""Property probe: pipeline-parallel gradients vs single-device gradients.

Verifies, leaf by leaf, that the SPMD circular pipeline's raw gradients are
the single-device gradients scaled uniformly by ``pp * tp`` — the rule
``make_pipeline_train_step`` normalizes by (see the derivation in
``parallel/pipeline.py``).  Runs in its own process so it can force an
arbitrary virtual device count (the test suite's conftest pins 8).

    python tools/grad_scale_probe.py --pp 4 --tp 4

Prints one JSON line: {"pp", "tp", "expected", "ratios": [...], "uniform"}.
Exit code 0 iff every leaf's median ratio equals pp*tp within 1% and the
per-leaf spread is under 2%.
"""

import argparse
import json
import os
import sys


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pp", type=int, default=2)
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--microbatches", type=int, default=2)
    args = ap.parse_args()
    n = args.pp * args.tp

    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    jax.config.update("jax_platforms", "cpu")

    import distributed_inference_demo_tpu.parallel.pipeline as pl
    from distributed_inference_demo_tpu.models import (
        KVCache, StageSpec, get_model_config)
    from distributed_inference_demo_tpu.models.decoder import (
        init_full_params, stage_forward)
    from distributed_inference_demo_tpu.parallel import MeshConfig, make_mesh

    # nkv=4 so tp up to 4 shards the kv heads evenly
    cfg = get_model_config("llama-test").replace(num_heads=8,
                                                 num_kv_heads=4)
    params = init_full_params(jax.random.PRNGKey(0), cfg)
    B, S = 8, 8
    ids = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                             cfg.vocab_size, jnp.int32)
    targets = jnp.roll(ids, -1, axis=1).at[:, -1].set(-100)

    def ref_loss(p):
        spec = StageSpec(0, 1, 0, cfg.num_layers)
        pos = jnp.broadcast_to(jnp.arange(S), (B, S))
        logits, _ = stage_forward(
            p, cfg, spec, ids, KVCache.create(cfg, cfg.num_layers, B, S),
            pos)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        mask = targets != -100
        ll = jnp.take_along_axis(logp, jnp.maximum(targets, 0)[..., None],
                                 -1)[..., 0]
        return -jnp.sum(jnp.where(mask, ll, 0)) / jnp.sum(mask)

    ref_grads = jax.grad(ref_loss)(params)

    mesh = make_mesh(MeshConfig(pp=args.pp, tp=args.tp), jax.devices()[:n])
    use_tp = args.tp > 1
    in_specs_params = pl._pp_in_specs(params, cfg, use_tp)
    sync_axes = pl._grad_sync_axes(params, cfg, use_tp)

    def sm(params_local, ids_mb, targets_mb):
        loss, grads = jax.value_and_grad(
            lambda p: pl.pipeline_apply(cfg, p, ids_mb, targets_mb,
                                        "tp" if use_tp else None)
        )(params_local)
        grads = jax.tree.map(
            lambda g, axes: jax.lax.psum(g, axes) if axes else g,
            grads, sync_axes)
        return loss, grads

    sharded = jax.shard_map(sm, mesh=mesh,
                            in_specs=(in_specs_params, P(), P()),
                            out_specs=(P(), in_specs_params),
                            check_vma=False)
    M = args.microbatches
    with mesh:
        _, grads = sharded(params, ids.reshape(M, B // M, S),
                           targets.reshape(M, B // M, S))

    def flat(tree):
        return {jax.tree_util.keystr(k): v for k, v in
                jax.tree_util.tree_leaves_with_path(tree)}

    refd, gd = flat(ref_grads), flat(grads)
    expected = float(args.pp * args.tp)
    report = []
    uniform = True
    for k, g in gd.items():
        r = np.asarray(g, np.float64).ravel()
        rr = np.asarray(refd[k], np.float64).ravel()
        m = np.abs(rr) > 1e-5
        if not m.any():
            continue
        q = r[m] / rr[m]
        med = float(np.median(q))
        spread = float(np.percentile(np.abs(q - med), 95))
        ok = abs(med - expected) <= 0.01 * expected and \
            spread <= 0.02 * max(1.0, abs(med))
        uniform &= ok
        report.append({"leaf": k, "median": round(med, 4),
                       "spread95": round(spread, 5), "ok": ok})
    print(json.dumps({"pp": args.pp, "tp": args.tp, "expected": expected,
                      "uniform": uniform,
                      "ratios": sorted({r["median"] for r in report}),
                      "leaves": len(report),
                      "bad": [r for r in report if not r["ok"]]}))
    return 0 if uniform else 1


if __name__ == "__main__":
    sys.exit(main())
