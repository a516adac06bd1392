"""Rating corpus of a deployment's published shape, made on the device
from ``--seed`` in one jitted call.

The shape comes from the configuration file: users × items, the rating
count, the value scale (``value_min``..``value_max`` in ``value_step``
steps) and the least ratings per user.  The marginals no README publishes
(activity spread, most ratings per user, popularity exponent, latent taste
model, biases, noise) are the file's ``assumed`` values.

The model is the program's calibrated surrogate (``repro.data.movielens``)
made vectorised: a rating is ``global_mean + user_bias + item_bias +
affinity_scale · p_u·q_i + noise``, rounded to the value step and clipped
to the scale; a user's items are drawn without replacement with
probability ∝ rank^-popularity_alpha (Gumbel top-k, the cut found per row
by bisection), and per-user counts are a log-normal activity scaled so the
counts sum to the published rating count exactly.  Every row is a function
of its user id and the seed alone, so the row blocks may overlap.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 2048          # users generated per step of the device loop
_BISECT_STEPS = 48        # float32 bisection converges in fewer


class CorpusShape(NamedTuple):
    """The numbers of a configuration that shape its corpus (hashable, so
    the jitted generator takes it as a static argument)."""
    n_users: int
    n_items: int
    n_ratings: int
    value_min: float
    value_max: float
    value_step: float
    min_user_ratings: int
    max_user_ratings: int
    latent_dim: int
    global_mean: float
    user_bias_std: float
    item_bias_std: float
    noise_std: float
    affinity_scale: float
    popularity_alpha: float
    activity_sigma: float


def corpus_shape(cfg: dict) -> CorpusShape:
    """The corpus shape of a configuration file's dict; refuses one whose
    counts cannot be met."""
    a = cfg["assumed"]
    shape = CorpusShape(
        n_users=int(cfg["n_users"]), n_items=int(cfg["n_items"]),
        n_ratings=int(cfg["n_ratings"]),
        value_min=float(cfg["value_min"]), value_max=float(cfg["value_max"]),
        value_step=float(cfg["value_step"]),
        min_user_ratings=int(cfg["min_user_ratings"]),
        max_user_ratings=min(int(a["max_user_ratings"]), int(cfg["n_items"])),
        latent_dim=int(a["latent_dim"]), global_mean=float(a["global_mean"]),
        user_bias_std=float(a["user_bias_std"]),
        item_bias_std=float(a["item_bias_std"]),
        noise_std=float(a["noise_std"]),
        affinity_scale=float(a["affinity_scale"]),
        popularity_alpha=float(a["popularity_alpha"]),
        activity_sigma=float(a["activity_sigma"]))
    lo = shape.n_users * shape.min_user_ratings
    hi = shape.n_users * shape.max_user_ratings
    if not lo <= shape.n_ratings <= hi:
        raise ValueError(f"{shape.n_ratings} ratings cannot be spread over "
                         f"{shape.n_users} users at {shape.min_user_ratings}"
                         f"..{shape.max_user_ratings} each")
    if shape.value_min < shape.value_step or shape.n_ratings >= 2**31:
        raise ValueError("0 marks an unrated cell: value_min must be at "
                         "least one step, and counts must fit int32")
    return shape


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative integer seed (wider than 32 bits
    too): the seed is hashed to two 32-bit words."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _user_counts(key, s: CorpusShape) -> jax.Array:
    """Ratings per user: ``floor(clip(lo + scale·activity, lo, hi))`` with
    the largest scale whose total stays ≤ the rating count, then one more
    rating for the users nearest their next integer until the total is
    exact."""
    act = jax.random.lognormal(key, s.activity_sigma, (s.n_users,))
    lo, hi = float(s.min_user_ratings), float(s.max_user_ratings)

    def floors(scale):
        return jnp.floor(jnp.clip(lo + scale * act, lo, hi)).astype(jnp.int32)

    def step(_, bounds):
        a, b = bounds
        mid = 0.5 * (a + b)
        under = jnp.sum(floors(mid)) <= s.n_ratings
        return jnp.where(under, mid, a), jnp.where(under, b, mid)

    top = jnp.float32(hi) / jnp.min(act)         # every user at hi
    scale, _ = jax.lax.fori_loop(0, 2 * _BISECT_STEPS, step,
                                 (jnp.float32(0.0), top))
    c = jnp.clip(lo + scale * act, lo, hi)
    base = jnp.floor(c).astype(jnp.int32)
    deficit = s.n_ratings - jnp.sum(base)
    frac = jnp.where(base < s.max_user_ratings, c - jnp.floor(c), -1.0)
    rank = jnp.argsort(jnp.argsort(-frac))
    return base + (rank < deficit).astype(jnp.int32)


def _row_cut(keys, counts):
    """Per row, a cut with exactly ``counts`` keys above it: midway
    between the ``counts``-th largest key (found by bisection) and the
    next, so that a key recomputed one rounding step apart still falls
    on the same side."""
    a = jnp.min(keys, axis=1) - 1.0
    b = jnp.max(keys, axis=1) + 1.0

    def step(_, bounds):
        a, b = bounds
        mid = 0.5 * (a + b)
        enough = jnp.sum(keys >= mid[:, None], axis=1) >= counts
        return jnp.where(enough, mid, a), jnp.where(enough, b, mid)

    a, _ = jax.lax.fori_loop(0, _BISECT_STEPS, step, (a, b))
    below = jnp.max(jnp.where(keys < a[:, None], keys, -jnp.inf), axis=1)
    return 0.5 * (a + below)


@functools.partial(jax.jit, static_argnames=("s",))
def generate(key: jax.Array, s: CorpusShape) -> jax.Array:
    """Dense ``(n_users, n_items)`` float32 ratings, 0 = unrated."""
    U, I = s.n_users, s.n_items
    k_p, k_q, k_ub, k_ib, k_perm, k_act, k_rows = jax.random.split(key, 7)
    d = s.latent_dim
    p = jax.random.normal(k_p, (U, d)) / np.sqrt(d)
    q = jax.random.normal(k_q, (I, d)) / np.sqrt(d)
    user_bias = s.user_bias_std * jax.random.normal(k_ub, (U,))
    item_bias = s.item_bias_std * jax.random.normal(k_ib, (I,))
    ranks = jax.random.permutation(k_perm, I) + 1.0
    log_pop = -s.popularity_alpha * jnp.log(ranks)
    counts = _user_counts(k_act, s)
    block = min(ROW_BLOCK, U)

    def rows(uids):
        keys = jax.vmap(lambda u: jax.random.split(
            jax.random.fold_in(k_rows, u)))(uids)             # (B, 2)
        gumbel = jax.vmap(lambda k: jax.random.gumbel(k, (I,)))(keys[:, 0])
        noise = jax.vmap(lambda k: jax.random.normal(k, (I,)))(keys[:, 1])
        draw = log_pop[None, :] + gumbel
        rated = draw > _row_cut(draw, counts[uids])[:, None]
        affinity = jnp.dot(p[uids], q.T,
                           precision=jax.lax.Precision.HIGHEST)
        raw = (s.global_mean + user_bias[uids, None] + item_bias[None, :]
               + s.affinity_scale * affinity + s.noise_std * noise)
        value = jnp.clip(jnp.round(raw / s.value_step) * s.value_step,
                         s.value_min, s.value_max)
        return jnp.where(rated, value, 0.0).astype(jnp.float32)

    def body(b, out):
        start = jnp.minimum(b * block, U - block)
        uids = start + jnp.arange(block)
        return jax.lax.dynamic_update_slice(out, rows(uids), (start, 0))

    n_blocks = -(-U // block)
    return jax.lax.fori_loop(0, n_blocks, body,
                             jnp.zeros((U, I), jnp.float32))


def make_corpus(cfg: dict, seed: int) -> jax.Array:
    """The configuration's corpus for ``seed``, on the default device."""
    return generate(seed_key(seed), corpus_shape(cfg))
