"""Beyond-paper: ε-accurate model evaluation with early termination.

    PYTHONPATH=src python examples/ola_eval_demo.py

Evaluates a (reduced) LM's per-token loss over many validation shards with
the bi-level estimator: shards are chunks, examples are tuples, and the eval
stops as soon as the mean loss is pinned to ±2% — typically after a small
fraction of the eval set.
"""

import numpy as np

import jax
import jax.numpy as jnp

from repro.compile_cache import use_compile_cache
from repro.configs import get_config
from repro.models import build_model
from repro.ola_ml.eval_ola import ola_eval


def main():
    cfg = get_config("smollm-135m", reduced=True)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))

    seq = 64
    loss_of = jax.jit(lambda tok: _per_example_loss(model, params, tok, cfg))

    rng = np.random.default_rng(0)
    shards = [rng.integers(0, cfg.vocab_size, (rng.integers(64, 128), seq + 1))
              .astype(np.int32) for _ in range(24)]

    res = ola_eval(lambda ex: np.asarray(loss_of(jnp.asarray(ex))),
                   shards, epsilon=0.02, batch=32, seed=1)
    total = sum(len(s) for s in shards)
    print(f"estimate      : {res.estimate:.4f}  [{res.lo:.4f}, {res.hi:.4f}]")
    print(f"error ratio   : {res.error_ratio:.4f} (target 0.02)")
    print(f"examples used : {res.examples_used}/{total} "
          f"({100 * res.examples_used / total:.1f}%) across "
          f"{res.shards_used} shards")

    # exhaustive reference
    full = np.concatenate([np.asarray(loss_of(jnp.asarray(s))) for s in shards])
    print(f"exhaustive    : {full.mean():.4f} "
          f"(bias {100 * abs(res.estimate - full.mean()) / full.mean():.2f}%)")


def _per_example_loss(model, params, toks, cfg):
    import repro.models.layers as L

    logits, _ = model.forward(params, toks[:, :-1])
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, toks[:, 1:, None], axis=-1)[..., 0]
    return -ll.mean(axis=-1)


if __name__ == "__main__":
    use_compile_cache()
    main()
