"""Faults planted underneath the timed path, for the tests that see
``correct`` come out false."""
import jax
import numpy as np


def _clear_engines():
    from repro.core import experiment as X

    X._compiled.cache_clear()


def plant(monkeypatch, fault: str) -> None:
    """Break the program as ``fault`` says; the compiled engines are
    dropped so the next call traces the broken code."""
    from repro.core import experiment as X
    from repro.dcsim import env as E

    step_epoch = E.step_epoch
    if fault == "state_unchanged":
        # the epoch returns the peak state it was given
        def broken(env, peak, ar, tau):
            return peak, step_epoch(env, peak, ar, tau)[1]
        monkeypatch.setattr(E, "step_epoch", broken)
    elif fault == "answer_altered":
        # each hour's metrics are produced for the next hour
        def broken(env, peak, ar, tau):
            return step_epoch(env, peak, ar, (tau + 1) % env.car.shape[1])
        monkeypatch.setattr(E, "step_epoch", broken)
    elif fault == "half_batch":
        # half the batch is left out; its rows report the mean of the rest
        run_batched = X._run_batched

        def broken(spec, env_b, state0, shard, faults=None):
            if not isinstance(env_b, E.EnvParams):
                env_b = E.stack_envs(env_b)
            n = int(env_b.er.shape[0])
            h = max(n // 2, 1)
            half = jax.tree_util.tree_map(lambda x: x[:h], env_b)
            if faults is not None and np.ndim(faults.avail_mult) == 3:
                faults = jax.tree_util.tree_map(lambda x: x[:h], faults)
            res = run_batched(spec.replace(seeds=spec.seeds[:h]), half,
                              state0, shard, faults)

            def fill(v):
                v = np.asarray(v)
                rest = np.broadcast_to(v.mean(axis=0), (n - h,) + v.shape[1:])
                return np.concatenate([v, rest.astype(v.dtype)])
            return {**res, "totals": {k: fill(v) for k, v in res["totals"].items()},
                    "per_epoch": {k: fill(v)
                                  for k, v in res["per_epoch"].items()}}
        monkeypatch.setattr(X, "_run_batched", broken)
    else:
        raise ValueError(fault)
    _clear_engines()
