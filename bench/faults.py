"""Faults planted in the timed path, to show that ``correct`` catches them.

Each takes the live ``WorkerSet`` and the ``Cell`` before warm-up and breaks
the program underneath the harness, which is otherwise left as it runs:

* ``unchanged_state``: the learner step computes its loss and returns the
  parameters and optimizer state it was given;
* ``half_batch``: the learner step leaves out half of each batch and takes
  the mean over the rest;
* ``altered_token``: every sampler's policy emits a different action from the
  one it scored (the log-probability and value stay those of the original);
* ``altered_bootstrap``: the postprocess's bootstrap values come out doubled
  (cells whose postprocess bootstraps with the critic, i.e. GAE).

Used by ``bench/tests/test_faults.py`` (CPU, tiny sizes) and by
``bench/calibrate.py --fault`` (on the chip, at the cell's size).
"""

from __future__ import annotations


def unchanged_state(ws, cell) -> None:
    lw = ws.local_worker()
    orig = lw._learn_jit

    def learn(params, target_params, opt_state, batch, key):
        _, _, loss, aux = orig(params, target_params, opt_state, batch, key)
        return params, opt_state, loss, aux

    lw._learn_jit = learn


def half_batch(ws, cell) -> None:
    lw = ws.local_worker()
    orig = lw._learn_jit

    def learn(params, target_params, opt_state, batch, key):
        n = next(iter(batch.values())).shape[0]
        keep = n // 2
        T = cell.traffic.get("rollout_len", 1) if cell.traffic["plan"] == "impala" else 1
        keep -= keep % T  # V-trace rows are whole length-T traces
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


FAULTS = {f.__name__: f for f in (unchanged_state, half_batch, altered_token, altered_bootstrap)}


def applies(name: str, cell) -> bool:
    """Whether the cell has the part that fault ``name`` breaks."""
    return name != "altered_bootstrap" or cell.traffic["plan"] == "ppo_lm"
