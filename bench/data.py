"""The benchmark's own data: the corpus, the query likelihood, the pool.

Copies of the program's generators (``repro.data.synthetic.make_corpus``'s
anisotropic Gaussian mixture, ``repro.core.likelihood.zipf_likelihood``
and ``sample_queries``), kept here so that a change to the program cannot
change what the benchmark serves.  The corpus is drawn on the device in
one jitted call from the seed and pulled to the host once; the mixture
keeps make_corpus's shapes and ranges (centres N(0, 4), per-cluster
log-normal axis scales, Dirichlet(2) cluster weights, optional unit norm
or uint8 range).
"""
from __future__ import annotations

import numpy as np


def seed_words(seed: int, stream: int) -> np.ndarray:
    """Two uint32 words for stream ``stream`` of ``seed``: any whole
    number, however large, maps to a key without overflow."""
    return np.random.SeedSequence([int(seed), stream]).generate_state(
        2, np.uint32)


def host_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def corpus(spec: dict, seed: int) -> tuple[np.ndarray, float]:
    """(n, d) float32 mixture corpus on the host and its standard
    deviation, drawn on the default device from ``seed``."""
    import jax
    import jax.numpy as jnp

    n, d, k = int(spec["n"]), int(spec["d"]), int(spec["mixture_clusters"])
    unit_norm = bool(spec.get("unit_norm", False))
    uint8_range = bool(spec.get("uint8_range", False))

    @jax.jit
    def draw(key_data):
        key = jax.random.wrap_key_data(key_data)
        kc, ks, kw, ka, kn = jax.random.split(key, 5)
        centers = 4.0 * jax.random.normal(kc, (k, d), jnp.float32)
        scales = jnp.exp(-0.5 + 0.6 * jax.random.normal(ks, (k, d),
                                                        jnp.float32))
        w = jax.random.dirichlet(kw, jnp.full((k,), 2.0, jnp.float32))
        # i.i.d. cluster draws by inverse CDF: the same law as
        # make_corpus's multinomial sizes followed by a shuffle
        cdf = jnp.cumsum(w)
        u = jax.random.uniform(ka, (n,), jnp.float32) * cdf[-1]
        a = jnp.minimum(jnp.searchsorted(cdf, u), k - 1)
        x = centers[a] + jax.random.normal(kn, (n, d), jnp.float32) * scales[a]
        if unit_norm:
            x = x / (jnp.linalg.norm(x, axis=1, keepdims=True) + 1e-12)
        if uint8_range:
            lo, hi = x.min(), x.max()
            x = jnp.round((x - lo) / (hi - lo) * 255.0)
        return x, jnp.std(x)

    x, std = draw(jnp.asarray(seed_words(seed, 0)))
    return np.asarray(x), float(std)


def zipf_likelihood(n: int, alpha: float = 1.0) -> np.ndarray:
    """Zipfian likelihood over ``n`` ranks (float64, sums to 1)."""
    w = np.arange(1, n + 1, dtype=np.float64) ** (-alpha)
    return w / w.sum()


def entity_likelihood(n: int, traffic: dict, seed: int) -> np.ndarray:
    """Zipf(alpha) over a seeded permutation of the entities: the
    likelihood the traffic draws from, and the one QLBT is boosted by."""
    perm = host_rng(seed, 1).permutation(n)
    return zipf_likelihood(n, float(traffic["zipf_alpha"]))[perm]


def sample_queries(rng: np.random.Generator, db: np.ndarray, std: float,
                   p: np.ndarray, n_queries: int, noise_scale: float):
    """Queries drawn from the entity distribution ``p`` (paper §4.2):
    each is its entity's embedding plus Gaussian noise of
    ``noise_scale`` times the corpus standard deviation.  Returns
    (queries float32, entity ids int32)."""
    n, d = db.shape
    ids = rng.choice(n, size=n_queries, p=p / p.sum())
    q = db[ids] + rng.normal(0.0, std * noise_scale, size=(n_queries, d))
    return q.astype(np.float32), ids.astype(np.int32)


def query_pool(db: np.ndarray, std: float, p: np.ndarray, traffic: dict,
               seed: int) -> np.ndarray:
    """The traffic's pregenerated pool of queries, from the seed."""
    q, _ = sample_queries(host_rng(seed, 2), db, std, p,
                          int(traffic["pool"]), float(traffic["noise"]))
    return q
