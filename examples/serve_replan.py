"""Replanning as a service: fit the utility forest û once on flock191
(the "calibration" constellation), then serve eq.-13 schedule replans for
*other* constellations from long-lived `ReplanService` instances — no
refit per constellation.

Three pieces of the framework meet here:

* **Forest transfer** (`repro.core.utility.transfer_ready`): the search
  featurization depends only on `s_max`, never on the satellite count, so
  the flock191-fitted forest answers starlink40/120/400 requests
  unchanged; `transfer_report` shows how far each serving constellation
  sits outside the calibration envelope (trees saturate out there — see
  docs/replanning.md).
* **Delta-window scoring** (`repro.fl.replan.ReplanService`): consecutive
  aggregation events reuse the cached rollout prefix over the overlapping
  horizon and simulate only the newly revealed window, with `maintain()`
  run between requests so frontier upkeep stays off the answer path.
* **The persistent-jit serving pattern** (`examples/serve_decode.py`):
  one process, jitted kernels compiled per batch bucket on first use and
  reused for every later request.

    PYTHONPATH=src python examples/serve_replan.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.core import connectivity as CN
from repro.core import staleness as SS
from repro.core.utility import (RandomForestRegressor, featurize,
                                transfer_report)
from repro.fl.replan import (ReplanService, calibrate_forest,
                             rollout_histograms)

S_MAX = 8
DAYS = 0.25                    # 24 fifteen-minute windows per preset


def calibrate(s_max: int = S_MAX) -> RandomForestRegressor:
    """Fit û on flock191 rollouts (`repro.fl.replan.calibrate_forest`)."""
    C = CN.connectivity_sets(CN.constellation_preset("flock191"), days=DAYS)
    return calibrate_forest(C, s_max=s_max)


def serve(preset: str, rf: RandomForestRegressor, *, I0: int = 12,
          steps: int = 6, num_candidates: int = 2000):
    """One serving session: stream `steps` consecutive aggregation events
    for `preset` through a persistent service, realizing each returned
    schedule's first action against the true protocol state."""
    C = CN.connectivity_sets(CN.constellation_preset(preset), days=DAYS)
    K = C.shape[1]
    rep = transfer_report(rf, featurize(rollout_histograms(C, s_max=S_MAX),
                                        1.0))
    print(f"{preset} (K={K}): in_envelope="
          f"{rep.get('in_envelope', 1.0):.2f}, "
          f"pred range [{rep['pred_min']:.3f}, {rep['pred_max']:.3f}]")

    svc = ReplanService(rf, I0=I0, num_candidates=num_candidates,
                        s_max=S_MAX, seed=0, min_pool=64)
    state = jax.tree.map(np.asarray, SS.bootstrap_state(K))
    ig = 0
    rng = np.random.default_rng(1)
    for i in range(steps):
        Cw = C[i:i + I0]
        t0 = time.perf_counter()
        plan = svc.replan(i, Cw, state, ig, 1.0, rng=rng)
        dt = (time.perf_counter() - t0) * 1e3
        print(f"  window {i:2d}: {svc.last_mode:5s} "
              f"{'(' + svc.last_reason + ')' if svc.last_reason else '':14s}"
              f"{dt:8.1f} ms  schedule={''.join(map(str, plan))}")
        svc.maintain()             # frontier upkeep between requests
        st, g, _ = SS.step(jax.tree.map(jnp.asarray, state), jnp.int32(ig),
                           jnp.asarray(C[i]), jnp.asarray(bool(plan[0])),
                           s_max=S_MAX, collect="none")
        state = jax.tree.map(np.asarray, st)
        ig = int(g)
    print(f"  stats: {svc.stats}")


def main():
    compile_cache.enable()
    rf = calibrate()
    print(f"calibrated on flock191: {rf.n_trees} trees, "
          f"{rf.n_features_} features\n")
    for preset in ["starlink40", "starlink120", "starlink400"]:
        serve(preset, rf)
        print()


if __name__ == "__main__":
    main()
