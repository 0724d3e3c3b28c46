"""Faults planted in the timed path, to show that ``correct`` catches them.

Each takes the live ``WorkerSet`` and the ``Cell`` before warm-up and breaks
the program underneath the harness, which is otherwise left as it runs:

* ``unchanged_state``: the learner step computes its loss and gradients
  through the worker's non-updating path (``_grad_jit``, which donates
  nothing) and returns the very parameters and optimizer state it was
  given, copying none;
* ``half_batch``: the learner step leaves out half of each batch and takes
  the mean over the rest;
* ``altered_token``: every sampler's policy emits a different action from the
  one it scored (the log-probability and value stay those of the original);
* ``altered_bootstrap``: the postprocess's bootstrap values come out doubled
  (cells whose postprocess bootstraps with the critic, i.e. GAE);
* ``one_epoch``: of each ``train()``'s learner steps only the first epoch's
  run; the later epochs' are skipped, returning the state they were given
  and the last loss (cells whose plan makes more than one pass).

Used by ``bench/tests/test_faults.py`` (CPU, tiny sizes) and by
``bench/calibrate.py --fault`` (on the chip, at the cell's size).
"""

from __future__ import annotations


def unchanged_state(ws, cell) -> None:
    lw = ws.local_worker()
    grads = lw._grad_jit

    def learn(params, target_params, opt_state, batch, key):
        _, loss, aux = grads(params, target_params, batch, key)
        return params, opt_state, loss, aux

    lw._learn_jit = learn


def half_batch(ws, cell) -> None:
    lw = ws.local_worker()
    orig = lw._learn_jit

    def learn(params, target_params, opt_state, batch, key):
        n = next(iter(batch.values())).shape[0]
        keep = n // 2
        keep -= keep % cell.plan.batch_unit(cell.traffic)
        return orig(params, target_params, opt_state, {k: v[:keep] for k, v in batch.items()}, key)

    lw._learn_jit = learn


def altered_token(ws, cell) -> None:
    for actor in ws.remote_workers().actors:
        policy = actor.target.policy
        name = "compute_actions_stateful" if hasattr(policy, "init_lane_state") else "compute_actions"
        orig = getattr(policy, name)

        def act(*a, _orig=orig, _n=policy.num_actions, **k):
            out = _orig(*a, **k)
            return ((out[0] + 1) % _n,) + tuple(out[1:])

        setattr(policy, name, act)


def altered_bootstrap(ws, cell) -> None:
    for actor in ws.remote_workers().actors:
        policy = actor.target.policy
        orig = policy.value
        policy.value = lambda params, obs, _orig=orig: 2.0 * _orig(params, obs)


def one_epoch(ws, cell) -> None:
    lw = ws.local_worker()
    orig = lw._learn_jit
    t = cell.traffic
    per_epoch = t["train_batch"] // t["minibatch"]
    calls = [0]
    last = []

    def learn(params, target_params, opt_state, batch, key):
        i, calls[0] = calls[0], calls[0] + 1
        if i % (per_epoch * cell.plan.learner_passes(t)) < per_epoch:
            out = orig(params, target_params, opt_state, batch, key)
            last[:] = out[2:]
            return out
        return (params, opt_state, *last)

    lw._learn_jit = learn


FAULTS = {f.__name__: f for f in (unchanged_state, half_batch, altered_token, altered_bootstrap,
                                  one_epoch)}


def applies(name: str, cell) -> bool:
    """Whether the cell has the part that fault ``name`` breaks."""
    if name == "altered_bootstrap":
        return bool(cell.plan.BOOTSTRAPS_WITH_CRITIC)
    if name == "one_epoch":
        return (cell.plan.learner_passes(cell.traffic) or 1) > 1
    return True
