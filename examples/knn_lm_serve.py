"""kNN-LM serving: the paper's join inside the LM serving path.

Builds a datastore of (hidden, next-token) pairs from a small corpus
and indexes the keys with the full retrieval stack (DESIGN.md §9.5):
a **ShardedKNNIndex** built with ``metric="ip"`` — maximum-inner-product
retrieval, the unembed's own geometry — fronted by the **KNNServer**
admission/micro-batching layer.  Every decode step's hidden states are
submitted as single-query requests; the server re-coalesces them into
the pow2-bucket batches the AOT engine cache serves compile-free.

Then serves batched generation where every step interpolates the LM
distribution with the kNN distribution over retrieved continuations
(λ·p_kNN + (1−λ)·p_LM), and shows the memorization effect: with
retrieval ON, prompts copied from the corpus continue with the
memorized text.

    PYTHONPATH=src python examples/knn_lm_serve.py
"""
import dataclasses
import os

# Split the host CPU into 4 devices so the datastore actually shards
# (one corpus partition per device, collective top-K merge).
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.configs.base import RetrievalConfig, get_smoke_config
from repro.core.hybrid import HybridConfig
from repro.launch.serve import generate
from repro.models import IndexRetriever, init_params
from repro.runtime.server import ServerConfig
from repro.utils import init_compile_cache


def main():
    init_compile_cache()
    cfg = dataclasses.replace(
        get_smoke_config("olmo_1b"),
        retrieval=RetrievalConfig(enabled=True, k=8, lam=0.9,
                                  temperature=1.0))
    params, _ = init_params(jax.random.PRNGKey(0), cfg)

    rng = np.random.default_rng(0)
    corpus = jnp.asarray(rng.integers(0, cfg.vocab_size, (6, 64)), jnp.int32)

    mesh = Mesh(np.asarray(jax.devices()), ("shard",))
    ds = IndexRetriever.build(
        params, cfg, [corpus], mesh=mesh,
        hybrid_config=HybridConfig(k=cfg.retrieval.k, metric="ip"),
        server_config=ServerConfig(deadline=5.0))
    print(f"[knn-lm] datastore: {ds.size} (hidden, next-token) pairs "
          f"indexed over {ds.index.n_shards} shards, metric=ip, "
          f"served through KNNServer")

    prompts = corpus[:4, :24]             # prefixes straight from the corpus
    want = np.asarray(corpus[:4, 24:32])  # their memorized continuations

    out_ret = np.asarray(generate(params, cfg, prompts, 8, ds=ds))
    out_base = np.asarray(generate(params, cfg, prompts, 8, ds=None))

    acc_ret = float((out_ret == want).mean())
    acc_base = float((out_base == want).mean())
    print(f"[knn-lm] continuation accuracy on memorized prompts:")
    print(f"    retrieval ON  (λ={cfg.retrieval.lam}): {acc_ret:5.1%}")
    print(f"    retrieval OFF                : {acc_base:5.1%}")
    assert acc_ret > acc_base, "retrieval should help on memorized text"

    m = ds.server.metrics()
    print(f"[knn-lm] server: {m['n_served']} served / "
          f"{m['n_shed_total']} shed over {m['n_batches']} batches, "
          f"p50 {m['p50_response_s'] * 1e3:.1f} ms")
    assert m["n_shed_total"] == 0, "no retrieval request should be shed"
    print("[knn-lm] retrieval head improves memorized continuations ✓")


if __name__ == "__main__":
    main()
