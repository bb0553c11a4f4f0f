"""PCDN meets the LM stack: train an l1-sparse logistic probe on frozen
transformer hidden states (DESIGN.md section 5 — where the paper's convex
solver plugs into the assigned architectures).

    PYTHONPATH=src python examples/sparse_probe.py [--arch yi-6b]

Builds a reduced backbone, extracts final hidden states for a synthetic
binary task (does the sequence contain a marker token?), and fits the
probe with PCDN — the feature axis (d_model) is exactly the axis the
distributed solver shards.
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.core import PCDNConfig, make_problem, solve
from repro.data.synthetic import train_accuracy
from repro.models.transformer import Model
from repro.launch.specs import train_batch_specs
from repro.launch.mesh import make_mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b", choices=list(ARCH_IDS))
    ap.add_argument("--samples", type=int, default=256)
    ap.add_argument("--seq", type=int, default=24)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, reduced=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    model = Model(cfg, mesh)
    params = model.init_params(jax.random.PRNGKey(0))

    # synthetic task: label = does token 7 appear in the sequence?
    rng = np.random.default_rng(1)
    feats, labels = [], []
    marker = 7
    from repro.models.layers import apply_embed
    for i in range(0, args.samples, 32):
        batch = train_batch_specs(cfg, batch=32, seq=args.seq,
                                  concrete=True, seed=i)
        toks = np.asarray(batch["tokens"]).copy()
        has = (toks == marker).any(axis=1)
        # flip half the negatives to positives by injection
        inject = rng.random(32) < 0.5
        toks[inject & ~has, 2] = marker
        batch["tokens"] = jnp.asarray(toks)
        has = (toks == marker).any(axis=1)
        # frozen-backbone features: mean-pooled final hidden state
        if cfg.family == "encdec":
            h = model.encode(params, batch["frames"])
        else:
            xin = apply_embed(cfg, params["embed"], batch["tokens"])
            if cfg.family == "vlm":
                xin = jnp.concatenate(
                    [batch["patches"].astype(xin.dtype), xin], axis=1)
            h = model.backbone(params, xin, jnp.arange(xin.shape[1]))
        feats.append(np.asarray(jnp.mean(h, axis=1), np.float32))
        labels.append(np.where(has, 1.0, -1.0).astype(np.float32))
    X = np.concatenate(feats)
    y = np.concatenate(labels)
    cut = int(0.8 * len(y))

    prob = make_problem(X[:cut], y[:cut], c=1.0)
    res = solve(prob, PCDNConfig(P=max(cfg.d_model // 4, 4),
                                 max_outer=200, tol_kkt=1e-3))
    acc = train_accuracy(X[cut:], y[cut:], np.asarray(res.w))
    nnz = int(np.sum(np.asarray(res.w) != 0))
    print(f"[sparse_probe] {args.arch}: probe acc={acc:.3f} "
          f"nnz={nnz}/{cfg.d_model} F={res.objective:.4f} "
          f"converged={res.converged}")
    assert acc > 0.5, "probe should beat chance"


if __name__ == "__main__":
    main()
