"""Sharded embedding tables — the recsys model-parallel hot path.

JAX has no EmbeddingBag and no CSR sparse; this module builds both pieces of
the system explicitly:

  * ``embedding_bag_xla`` — multi-hot gather + ``segment_sum`` (the XLA
    formulation; the Pallas scalar-prefetch kernel in ``repro.kernels`` is
    the TPU-native version of the same op).
  * ``ShardedEmbedding`` — a fused big table row-sharded over *all* mesh
    devices with an explicit shard_map bucket → all_to_all → local gather →
    all_to_all pipeline (the DLRM/FBGEMM pattern: model-parallel embeddings
    under a data-parallel dense model).  Small tables are replicated (hot
    rows on tiny vocabularies would otherwise hammer one shard — the
    standard mitigation).

The bucket capacity is a static bound on lookups routed to any one shard
from one device; with per-field hashing of rows across shards and the
small-table replication policy, Poisson tail bounds make overflow
probability negligible at the configured slack (validated in tests, and the
lookup degrades to dropping the overflow — never corrupting other rows).

This is the paper's thread decomposition applied to storage: each "thread"
(device) owns an independent slice of the model state, and queries are
scattered to whichever thread owns them — similarity statistics in the CF
core, embedding rows here.
"""

from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


REPLICATE_THRESHOLD = 8192      # tables smaller than this are replicated


def embedding_bag_xla(table: jnp.ndarray, indices: jnp.ndarray, *,
                      combiner: str = "sum") -> jnp.ndarray:
    """(V, D) × (B, L) with -1 padding → (B, D).  Pure-XLA embedding bag."""
    valid = indices >= 0
    rows = jnp.take(table, jnp.where(valid, indices, 0), axis=0)
    rows = rows * valid[..., None].astype(table.dtype)
    out = jnp.sum(rows, axis=1)
    if combiner == "mean":
        out = out / jnp.maximum(jnp.sum(valid, axis=1, keepdims=True),
                                1).astype(out.dtype)
    return out


@dataclasses.dataclass(frozen=True)
class TableLayout:
    """Static layout: which fields live in the sharded vs replicated table."""
    field_sizes: Tuple[int, ...]          # vocab per field
    embed_dim: int
    n_shards: int                          # total devices rows shard over
    replicate_threshold: int = REPLICATE_THRESHOLD
    bucket_slack: float = 2.0

    @property
    def sharded_fields(self) -> Tuple[int, ...]:
        return tuple(i for i, s in enumerate(self.field_sizes)
                     if s >= self.replicate_threshold)

    @property
    def replicated_fields(self) -> Tuple[int, ...]:
        return tuple(i for i, s in enumerate(self.field_sizes)
                     if s < self.replicate_threshold)

    def _field_offset(self, field: int) -> int:
        """Offset of ``field``'s rows within its (sharded|replicated) table."""
        home = self.sharded_fields if field in self.sharded_fields \
            else self.replicated_fields
        off = 0
        for f in home:
            if f == field:
                return off
            off += self.field_sizes[f]
        raise KeyError(field)

    @property
    def sharded_rows(self) -> int:
        n = sum(self.field_sizes[f] for f in self.sharded_fields)
        rem = n % self.n_shards                  # pad to divide over shards
        return n + (self.n_shards - rem if rem else 0)

    @property
    def replicated_rows(self) -> int:
        return max(sum(self.field_sizes[f] for f in self.replicated_fields),
                   1)

    def global_ids(self, indices: jnp.ndarray, fields: Sequence[int],
                   ) -> jnp.ndarray:
        """Per-field ids (B, |fields|) → fused-table row ids.

        Offsets are absolute per field (stable under subset lookups).
        """
        offs = jnp.asarray([self._field_offset(f) for f in fields],
                           jnp.int32)
        return indices + offs[None, :]

    def total_params(self) -> int:
        return (self.sharded_rows + self.replicated_rows) * self.embed_dim


def init_tables(layout: TableLayout, key, scale: float = 0.01):
    k1, k2 = jax.random.split(key)
    return {
        "sharded": jax.random.normal(
            k1, (layout.sharded_rows, layout.embed_dim), jnp.float32) * scale,
        "replicated": jax.random.normal(
            k2, (layout.replicated_rows, layout.embed_dim),
            jnp.float32) * scale,
    }


def table_specs(batch_axes=("pod", "data", "model")):
    return {"sharded": P(batch_axes, None), "replicated": P(None, None)}


def _bucketed_exchange_lookup(local_table, owner, local_row, n_shards: int,
                              capacity: int, axis_names):
    """shard_map body: route each lookup to its owner shard and back.

    ``owner``/``local_row``: (L,) for this device's L lookups.  Returns
    (L, D) gathered rows.  Overflow beyond ``capacity`` per destination
    bucket returns zeros (never corrupts other lookups).
    """
    L = owner.shape[0]
    d = local_table.shape[1]
    # slot each lookup into its destination bucket
    onehot = jax.nn.one_hot(owner, n_shards, dtype=jnp.int32)       # (L, N)
    pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1          # (L,)
    keep = pos < capacity
    slot_o = jnp.where(keep, owner, n_shards)                        # drop row
    slot_p = jnp.where(keep, pos, 0)

    send_rows = jnp.zeros((n_shards + 1, capacity), jnp.int32)
    send_rows = send_rows.at[slot_o, slot_p].set(local_row, mode="drop")
    send_rows = send_rows[:n_shards]                                 # (N, C)

    recv_rows = jax.lax.all_to_all(send_rows, axis_names, split_axis=0,
                                   concat_axis=0, tiled=True)        # (N, C)
    vals = jnp.take(local_table, recv_rows.reshape(-1), axis=0,
                    mode="clip").reshape(n_shards, capacity, d)
    back = jax.lax.all_to_all(vals, axis_names, split_axis=0,
                              concat_axis=0, tiled=True)             # (N, C, D)

    out = back[slot_o.clip(0, n_shards - 1), slot_p]                 # (L, D)
    return jnp.where(keep[:, None], out, 0.0)


def sharded_lookup(layout: TableLayout, tables, indices: jnp.ndarray,
                   mesh: Mesh | None, *, fields: Sequence[int] | None = None,
                   batch_axes=("pod", "data", "model")) -> jnp.ndarray:
    """(B, F) per-field ids → (B, F, D) embeddings.

    Sharded fields go through the all_to_all exchange; replicated fields are
    local takes.  ``indices`` must be batch-sharded over ``batch_axes``.
    With ``mesh=None`` (single device / tests) the dense fallback runs.
    ``fields`` selects which layout fields the index columns correspond to
    (default: all, in order) — subset lookups keep absolute offsets.
    """
    all_fields = tuple(fields) if fields is not None \
        else tuple(range(len(layout.field_sizes)))
    b, f = indices.shape
    assert f == len(all_fields)
    d = layout.embed_dim
    sf_pos = [i for i, fl in enumerate(all_fields)
              if fl in layout.sharded_fields]
    rf_pos = [i for i, fl in enumerate(all_fields)
              if fl in layout.replicated_fields]
    sf = tuple(all_fields[i] for i in sf_pos)
    rf = tuple(all_fields[i] for i in rf_pos)
    out = jnp.zeros((b, f, d), tables["sharded"].dtype)

    if rf:
        ids = layout.global_ids(indices[:, rf_pos], rf)
        vals = jnp.take(tables["replicated"], ids, axis=0)
        out = out.at[:, rf_pos].set(vals)

    if sf:
        ids = layout.global_ids(indices[:, sf_pos], sf)             # (B, Fs)
        if mesh is None:
            vals = jnp.take(tables["sharded"], ids, axis=0)
        else:
            if batch_axes == ("pod", "data", "model"):
                batch_axes = tuple(mesh.axis_names)      # adapt to the mesh
            n = int(np.prod([mesh.shape[a] for a in batch_axes]))
            # layout.n_shards is the padding granularity; the actual shard
            # count comes from the mesh and must divide the padded rows
            assert layout.sharded_rows % n == 0, (layout.sharded_rows, n)
            rows_per_shard = layout.sharded_rows // n
            l_loc = (b // n) * len(sf)
            # slack-scaled buckets at production sizes; small per-device
            # lookup counts get full capacity so the exchange stays exact
            # (skew can put every lookup in one bucket)
            capacity = max(int(l_loc / n * layout.bucket_slack),
                           min(l_loc, 64))

            def body(tbl_loc, ids_loc):
                flat = ids_loc.reshape(-1)
                owner = flat // rows_per_shard
                local_row = flat % rows_per_shard
                got = _bucketed_exchange_lookup(
                    tbl_loc, owner, local_row, n, capacity, batch_axes)
                return got.reshape(ids_loc.shape + (d,))

            vals = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(batch_axes, None), P(batch_axes, None)),
                out_specs=P(batch_axes, None, None),
                check_vma=False,
            )(tables["sharded"], ids)
        out = out.at[:, sf_pos].set(vals)
    return out
