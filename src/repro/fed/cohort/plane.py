"""Device-resident data plane: upload the task once, gather per round.

The legacy pipeline copies O(C * K_max * B * sample) fresh data bytes to the
device every round.  The plane inverts that: every distinct sample lives on
the device ONCE (the *bank*), and a round is materialized in-jit by gathering
bank rows through the round's [C, K_max, B] index matrix.  The host ships
only the index plan — int32 indices and O(cohort) scalars.

Two bank layouts:

* **procedural** — the task exposes ``bank()`` (a small pytree of [N, ...]
  arrays) and ``bank_rows(client_ids, idx)`` (a pure broadcast-arithmetic map
  from (client, local sample id) to bank row).  Zero per-client metadata:
  million-client populations cost O(bank) device memory.
* **table** — fallback for any task: each client's samples are materialized
  once through ``task.batch`` into a flat [total_samples, ...] bank with an
  offsets vector.  O(sum |D_i|) upload, still O(cohort) per round.

``DevicePlane.materialize(plan)`` is the jit-traceable step that turns an
``IndexPlan`` into the ``RoundBatch`` the round driver consumes, generating
RR indices on device (``kernels.rr_perm``) when the plan carries none.
Bitwise contract: a gather returns exactly the floats ``task.batch`` would
have produced, so with host-generated indices the materialized batch equals
the legacy path bit-for-bit.  The fleet plane (``repro.fed.fleet``) never
touches the plane: fault cuts and buffered-tick cohorts are realized in the
host index plan, whose meta (staleness / arrive_time / dropped included)
passes through ``materialize`` untouched.

The *data* bank here is immutable and round-independent.  Its mutable
sibling — the per-client **state bank** of stateful local chains (SCAFFOLD
control variates etc.) — is also device-resident but rides
``ServerState.clients`` instead, because it must evolve with the round
sequence: the round step gathers the cohort's ``[C, ...]`` rows in-jit and
slot-order scatters the finalized rows back (``repro.fed.rounds``), keeping
per-round state traffic O(cohort) while plans prefetch ahead.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ...configs.base import FLConfig
from ...data.federated import (Bucket, BucketedBatch, BucketedPlan, IndexPlan,
                               Population, RoundBatch)
from ...kernels.rr_perm.ops import rr_indices
from ...kernels.rr_perm.ref import stream_key


@dataclass
class DevicePlane:
    """An uploaded task bank + the round materialization rule."""

    bank: Any                      # pytree, leaves jnp [N, ...] (device)
    rows_fn: Callable              # (client_ids [C], idx [C,K,B]) -> rows [C,K,B]
    fl: FLConfig
    mode: str = "rr"               # "rr" | "wr" (equalized / no-reshuffle)
    rr_backend: str = "host"       # host | host_feistel | device_ref | device
    interpret: bool | None = None  # Pallas interpret override (None = auto)

    def gather(self, client_ids, idx):
        """Bank rows for (clients, indices) -> data pytree [C, K, B, ...]."""
        rows = self.rows_fn(client_ids, idx)
        return jax.tree.map(lambda leaf: jnp.take(leaf, rows, axis=0), self.bank)

    def _indices(self, client_id, sizes, spe, rnd, K: int):
        """Regenerate RR streams in-jit (stateless, O(slots)).  The streams
        are counter-based per (epoch, position), so a K < K_max generation is
        exactly the K-step prefix of the full stream — which is what keeps
        bucketed rounds bitwise-identical to padded ones."""
        prekey = stream_key(self.fl.seed, client_id.astype(jnp.uint32),
                            rnd.astype(jnp.uint32), jnp)
        backend = "pallas" if self.rr_backend == "device" else "ref"
        return rr_indices(prekey, sizes, spe,
                          B=self.fl.local_batch, K=K,
                          rounds=self.fl.rr_rounds, mode=self.mode,
                          backend=backend, interpret=self.interpret)

    def device_indices(self, plan: IndexPlan):
        """Regenerate the round's RR streams in-jit (stateless, O(cohort))."""
        return self._indices(plan.meta.client_id, plan.sizes, plan.spe,
                             plan.rnd, int(plan.step_mask.shape[1]))

    def materialize(self, plan: "IndexPlan | BucketedPlan") -> "RoundBatch | BucketedBatch":
        """Index plan -> round batch, inside the jitted round step."""
        with jax.named_scope("plan_materialize"):
            if isinstance(plan, BucketedPlan):
                buckets = []
                for b in plan.buckets:
                    cids = jnp.take(plan.meta.client_id, b.slots, axis=0)
                    idx = b.idx
                    if idx is None:
                        idx = self._indices(cids,
                                            jnp.take(plan.sizes, b.slots, axis=0),
                                            jnp.take(plan.spe, b.slots, axis=0),
                                            plan.rnd, int(b.step_mask.shape[1]))
                    data = self.gather(cids.astype(jnp.int32), idx)
                    buckets.append(Bucket(data=data, idx=None,
                                          step_mask=b.step_mask, slots=b.slots))
                return BucketedBatch(buckets=tuple(buckets), meta=plan.meta,
                                     pos=plan.pos)
            idx = plan.idx if plan.idx is not None else self.device_indices(plan)
            data = self.gather(plan.meta.client_id.astype(jnp.int32), idx)
            return RoundBatch(data=data, step_mask=plan.step_mask, meta=plan.meta)


def _table_bank(task, population: Population):
    """Materialize every client's samples once -> flat bank + offsets."""
    sizes = np.asarray(population.sizes, dtype=np.int64)
    offsets = np.zeros(len(sizes) + 1, dtype=np.int64)
    np.cumsum(sizes, out=offsets[1:])
    parts = []
    for cid, n_i in enumerate(sizes):
        sample = task.batch(cid, np.arange(int(n_i)).reshape(1, -1))
        parts.append({k: v[0] for k, v in sample.items()})
    bank = {k: np.concatenate([p[k] for p in parts], axis=0) for k in parts[0]}
    offs = jnp.asarray(offsets[:-1], jnp.int32)

    def rows_fn(client_ids, idx):
        return jnp.take(offs, client_ids, axis=0)[:, None, None] + idx

    return bank, rows_fn


def build_plane(task, population: Population, fl: FLConfig, *,
                rr_backend: str | None = None,
                interpret: bool | None = None) -> DevicePlane:
    """Upload the task's data plane for (task, population, fl)."""
    from ..strategy import equalized_mode  # deferred: avoids import cycle

    if hasattr(task, "bank") and hasattr(task, "bank_rows"):
        bank_np, rows_fn = task.bank(), task.bank_rows
    else:
        bank_np, rows_fn = _table_bank(task, population)
    bank = jax.tree.map(jnp.asarray, bank_np)
    mode = "wr" if (equalized_mode(fl.algorithm) is not None or not fl.reshuffle) else "rr"
    return DevicePlane(bank=bank, rows_fn=rows_fn, fl=fl, mode=mode,
                       rr_backend=rr_backend or fl.rr_backend,
                       interpret=interpret)
