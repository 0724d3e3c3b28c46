"""Sharded SPMD learner group: data-parallel ``learn_on_batch`` on a mesh.

The paper's thesis is that the dataflow layer and the numerical concerns
compose independently (§3, Fig 5): ``TrainOneStep`` / ``LearnerThread`` call
``learn_on_batch`` and never care *how* the update executes.  This module is
the numerical half of that contract scaled out: it lowers a worker's learn
step onto a ``jax.Mesh`` so the same dataflow plan drives one device or a
data-parallel learner group — the execution mapping changes, the graph does
not (MSRL's "fragment to multiple processes" move, SRL's learner group).

``ShardedLearnerGroup`` wraps an existing rollout/learner worker (the owner
of policy, params, optimizer, RNG) and replaces its ``learn_on_batch`` with
a jit-compiled SPMD step:

  * **batch sharding at the transport boundary** — host numpy columns are
    ``device_put`` directly with a ``NamedSharding`` over the mesh's
    ``data`` axis (resolved through the existing ``AxisRules`` table), so
    each device receives only its slice; no full-batch staging on device 0.
  * **per-device gradients** — the loss and its gradient run under
    ``shard_map``, one program per device over its rows, then ``pmean``
    over ``data``.  XLA cannot partition a Pallas TPU kernel, so a loss
    that calls one (flash attention, the fused PPO surrogate) must be
    written per device rather than left to the SPMD partitioner.
  * **gradient microbatch accumulation** — the per-device shard is split
    into ``microbatch`` slices walked by ``lax.scan``, accumulating the
    mean gradient before a single optimizer apply: global batch sizes
    beyond per-device memory cost activations of one microbatch only.
  * **donated buffers** — optimizer state is donated into the step, so its
    update is in-place on device.  Param donation is opt-in
    (``donate_params=True``): on the thread backend ``sync_weights`` shares
    the canonical param arrays with rollout workers *by reference*, and
    donating them would delete the buffers out from under the workers'
    jitted rollouts (a real crash, caught end-to-end on IMPALA).  Enable it
    only when weights cross every worker boundary by value (process
    backends).

Loss parity: with equal global batch, mean-reduced losses and gradients are
identical (to float tolerance) between 1 device, N devices, and any
microbatch factor — asserted at 1e-4 by ``tests/test_learner_group.py``
against a 4-device simulated mesh (``XLA_FLAGS=--xla_force_host_platform_
device_count=4``).
"""

from __future__ import annotations

import logging
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.metrics import policy_lag, span
from repro.distributed.sharding import AxisRules, make_data_mesh

PyTree = Any

__all__ = ["ShardedLearnerGroup"]

logger = logging.getLogger(__name__)

# Logical-axis rules for the learner group's mesh: only the batch dim is
# sharded (pure data parallelism); params/opt state stay replicated.
LEARNER_RULES = {"batch": "data"}


class ShardedLearnerGroup:
    """Data-parallel SPMD learn step over ``num_learners`` devices.

    ``worker`` must expose the learner half of the worker protocol —
    ``policy``, ``params``, ``target_params``, ``opt_state``, ``optimizer``,
    ``_key``, and the pure ``_loss_for(params, target_params, batch, key)``
    (``RolloutWorker`` does).  The group keeps the worker canonical: after
    every step the worker's params/opt state are the updated (replicated)
    values, so ``get_weights``/``sync_weights`` see fresh weights.
    """

    def __init__(
        self,
        worker: Any,
        num_learners: int = 0,
        microbatch: int = 0,
        donate_params: bool = False,
    ):
        devices = jax.devices()
        requested = num_learners if num_learners > 0 else 1
        if requested > len(devices):
            logger.warning(
                "learner group: %d learners requested but only %d devices "
                "visible; clamping (set XLA_FLAGS="
                "--xla_force_host_platform_device_count=%d to simulate)",
                requested, len(devices), requested,
            )
        self.num_learners = min(requested, len(devices))
        self.microbatch = max(microbatch, 1)
        self.donate_params = donate_params
        self.worker = worker
        # Trace-structured losses (v-trace) reshape rows back into
        # contiguous length-T traces: trimming and microbatch slicing must
        # then happen in whole-trace units or the reshape fails (or worse,
        # regroups rows across trace boundaries silently).
        policy = getattr(worker, "policy", None)
        self.trace_len = (
            max(int(getattr(policy, "rollout_len", 0)), 1)
            if getattr(policy, "loss_kind", None) == "vtrace"
            else 1
        )
        self.mesh = make_data_mesh(self.num_learners)
        self.rules = AxisRules(LEARNER_RULES, self.mesh)
        self._batch_sharding = NamedSharding(
            self.mesh, self.rules.resolve(("batch",))
        )
        self._replicated = NamedSharding(self.mesh, P())
        self._step = None
        self.num_steps = 0
        self.num_rows_trimmed = 0
        # Replicate the worker's state onto the mesh once; afterwards the
        # donated step keeps it resident.
        for attr in ("params", "target_params", "opt_state"):
            setattr(
                self.worker,
                attr,
                jax.device_put(getattr(self.worker, attr), self._replicated),
            )

    # ------------------------------------------------------------ SPMD step
    def _build_step(self):
        optimizer = self.worker.optimizer
        loss_for = self.worker._loss_for
        k = self.microbatch
        n = self.num_learners
        # Per-row aux leaves (td_error) carry the row axis; scalar ones
        # (stacked to [k] under microbatching) are means over rows.
        scalar_ndim = 1 if k > 1 else 0
        row_spec = P(None, "data") if k > 1 else P("data")

        def local_grads(params, target_params, batch, key):
            """One device's shard: mean gradient and loss over its rows."""
            if n > 1:
                # Shards draw independent noise (stochastic losses, SAC).
                key = jax.random.fold_in(key, jax.lax.axis_index("data"))
            if k > 1:
                def microstep(carry, mb):
                    grad_acc, loss_acc, key = carry
                    key, sub = jax.random.split(key)
                    (loss, aux), grads = jax.value_and_grad(
                        loss_for, has_aux=True
                    )(params, target_params, mb, sub)
                    grad_acc = jax.tree_util.tree_map(jnp.add, grad_acc, grads)
                    return (grad_acc, loss_acc + loss, key), aux

                zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
                (grads, loss, _), aux = jax.lax.scan(
                    microstep, (zeros, jnp.asarray(0.0), key), batch
                )
                grads = jax.tree_util.tree_map(lambda g: g / k, grads)
                loss = loss / k
                # aux leaves keep their stacked [k, ...] leading axis; the
                # host side means scalars and flattens per-row columns.
            else:
                (loss, aux), grads = jax.value_and_grad(loss_for, has_aux=True)(
                    params, target_params, batch, key
                )
            # Equal shards of a row-mean loss: the global mean is the mean
            # of the shard means.
            mean = lambda x: jax.lax.pmean(x, "data")  # noqa: E731
            scalars = {a: mean(v) for a, v in aux.items() if v.ndim <= scalar_ndim}
            rows = {a: v for a, v in aux.items() if v.ndim > scalar_ndim}
            return jax.tree_util.tree_map(mean, grads), mean(loss), scalars, rows

        # Pallas TPU kernels in the loss cannot be partitioned by XLA, so
        # the gradient is an explicit per-device program (shard_map) whose
        # results are averaged over the data axis.
        sharded_grads = jax.shard_map(
            local_grads,
            mesh=self.mesh,
            in_specs=(P(), P(), row_spec, P()),
            out_specs=(P(), P(), P(), row_spec),
            check_vma=False,
        )

        def step(params, target_params, opt_state, batch, key):
            grads, loss, scalars, rows = sharded_grads(
                params, target_params, batch, key
            )
            params, opt_state = optimizer.apply(params, grads, opt_state)
            return params, opt_state, loss, {**scalars, **rows}

        return jax.jit(
            step,
            # opt_state (2) updates in place on the mesh; params (0) only
            # when donation is safe (see class docstring), and
            # target_params persist across steps and are never donated.
            donate_argnums=(0, 2) if self.donate_params else (2,),
            out_shardings=(self._replicated, self._replicated, None, None),
        )

    # --------------------------------------------------- transport boundary
    def shard_batch(self, batch: Any) -> Tuple[Dict[str, jax.Array], int]:
        """Host columns -> mesh-sharded device columns.

        The global row count must tile evenly: each of the ``microbatch``
        slices must split across ``num_learners`` devices, and for
        trace-structured losses every slice must hold whole length-T traces
        (batch-major rows keep traces contiguous, so tail-trimming in
        T-multiples preserves them).  Surplus rows are trimmed (counted in
        ``num_rows_trimmed``) rather than padded — padding would silently
        bias mean-reduced losses.  With ``microbatch=k`` columns land as
        [k, rows/k, ...], microbatch axis replicated, row axis sharded over
        ``data``.
        """
        # rows-per-microbatch must divide by trace_len (loss reshape) and
        # the total by num_learners (even device shards): k * lcm(n, T).
        import math

        tile = self.microbatch * math.lcm(self.num_learners, self.trace_len)
        count = batch.count if hasattr(batch, "count") else len(next(iter(batch.values())))
        usable = (count // tile) * tile
        if usable == 0:
            raise ValueError(
                f"batch of {count} rows cannot tile {self.num_learners} "
                f"learners x {self.microbatch} microbatches"
            )
        self.num_rows_trimmed += count - usable
        k = self.microbatch
        if k > 1:
            sharding = NamedSharding(self.mesh, P(None, "data"))
        else:
            sharding = self._batch_sharding
        out = {}
        for name, col in batch.items():
            # Host-only metadata never reaches the mesh: batch_indices feed
            # replay priority updates, eps_id is int64 fragment labeling
            # (canonicalizing it to int32 would overflow the lane strides).
            if name in ("batch_indices", "eps_id"):
                continue
            col = np.asarray(col)[:usable]
            if k > 1:
                col = col.reshape((k, usable // k) + col.shape[1:])
            out[name] = jax.device_put(col, sharding)
        return out, usable

    # -------------------------------------------------------------- learning
    def learn_on_batch(self, batch: Any, policy_id: Optional[str] = None) -> Dict[str, Any]:
        if self._step is None:
            self._step = self._build_step()
        w = self.worker
        with span("learner.learn", **policy_lag(getattr(w, "weights_version", None), batch)):
            with span("learner.h2d"):
                device_batch, usable = self.shard_batch(batch)
            with span("learner.step"):
                w._key, key = jax.random.split(w._key)
                w.params, w.opt_state, loss, aux = self._step(
                    w.params, w.target_params, w.opt_state, device_batch, key
                )
            self.num_steps += 1
            # Replay the worker's own per-update side effects (the weight
            # version, SAC polyak target tracking — skipping it would train
            # against a frozen target forever, silently), then keep the
            # touched state on-mesh.
            if hasattr(w, "_post_update"):
                w._post_update()
                w.target_params = jax.device_put(w.target_params, self._replicated)
            with span("learner.fetch"):
                return self._info(batch, usable, loss, aux)

    def _info(self, batch: Any, usable: int, loss: Any, aux: Dict[str, Any]) -> Dict[str, Any]:
        count = batch.count if hasattr(batch, "count") else usable
        info: Dict[str, Any] = {"loss": float(loss)}
        for name, v in aux.items():
            if name == "td_error":
                # Per-row priorities: flatten the microbatch axis back out.
                td = np.asarray(v).reshape(-1)
                if td.size < count:
                    # Trimmed rows got no update; consumers zip td_error
                    # with the *full* batch (UpdateReplayPriorities against
                    # batch_indices), so pad with the mean magnitude — a
                    # neutral priority, not an artificial zero or max.
                    fill = float(np.mean(np.abs(td))) if td.size else 0.0
                    td = np.concatenate([td, np.full(count - td.size, fill, td.dtype)])
                info["td_error"] = td
            else:
                info[name] = float(jnp.mean(v))
        info["num_learners"] = self.num_learners
        info["microbatch"] = self.microbatch
        return info

    # ----------------------------------------------------- worker protocol
    def get_weights(self) -> PyTree:
        return self.worker.get_weights()

    def set_weights(self, weights: PyTree) -> None:
        self.worker.set_weights(weights)
        self.worker.params = jax.device_put(self.worker.params, self._replicated)

    def __repr__(self) -> str:  # pragma: no cover
        return (
            f"ShardedLearnerGroup(devices={self.num_learners}, "
            f"microbatch={self.microbatch}, steps={self.num_steps})"
        )
