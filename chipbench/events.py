"""Network events of a traffic mix, drawn from the seed, and the steps at
which they reach the trainer.

``link_degradation`` is a copy of the program's cross-region generator
(``repro.scenarios.generators.link_degradation``): links flap to a
severity drawn from ``severity_range`` of nominal and are repaired after
an exponential delay, both as scale-mode bandwidth events.  The mix gives
the generator's parameters in units of events; the events are taken in
time order and placed one every ``every_steps`` steps, so every seed has
the same arrivals and only the severities differ.
"""

from __future__ import annotations

import random


def _poisson_times(rng: random.Random, rate: float, horizon: float):
    t, times = 0.0, []
    while True:
        t += rng.expovariate(rate)
        if t >= horizon:
            return times
        times.append(t)


def link_degradation(rng: random.Random, horizon: float, *, selector: str,
                     rate: float, severity_range, repair_mean: float):
    """(time, factor) pairs: a degrade, then its repair by the reciprocal."""
    out = []
    for t in _poisson_times(rng, rate, horizon):
        sev = rng.uniform(*severity_range)
        out.append((round(t, 6), sev))
        back = t + rng.expovariate(1.0 / repair_mean)
        if back < horizon:
            out.append((round(back, 6), 1.0 / sev))
    return sorted(out, key=lambda e: e[0])


GENERATORS = {"link_degradation": link_degradation}


def schedule(spec: dict, seed: int, count: int):
    """``count`` events as (step, selector, factor): the first at
    ``spec["setup_step"]``, inside set-up, then from
    ``spec["first_window_step"]`` on one every ``spec["every_steps"]``."""
    gen = GENERATORS[spec["generator"]]
    rng = random.Random(seed)
    horizon = float(4 * count)
    drawn = []
    while len(drawn) < count:
        drawn = gen(rng, horizon, selector=spec["selector"], rate=1.0,
                    severity_range=tuple(spec["severity_range"]),
                    repair_mean=spec["repair_mean"])
        horizon *= 2
    steps = [spec["setup_step"]] + [spec["first_window_step"]
                            + k * spec["every_steps"]
                            for k in range(count - 1)]
    return [(s, spec["selector"], f) for s, (_, f) in zip(steps, drawn)]


def topology(spec: dict):
    from repro.core import multi_pod_tpu
    builders = {"multi_pod_tpu": multi_pod_tpu}
    args = {k: v for k, v in spec.items() if k != "kind"}
    return builders[spec["kind"]](**args)
