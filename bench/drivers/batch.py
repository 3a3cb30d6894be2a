"""Driver ``batch``: planned transforms through a gearshifft client.

Set-up builds one client per problem and kind (the config's ``client``,
registered by name, at its ``rigor``, through one ``Session``'s plan
cache), makes each input on the device from the seed, and runs each
client's forward and inverse once, so that every executable the window
uses is built and has run.  The window runs whole passes over the clients
in a fixed order, each doing ``execute_forward`` then ``execute_inverse``
(each ends in ``block_until_ready``), until ``seconds`` have passed.

The check drives the same clients and executables once more, after the
window, on the seed's inputs: the forward spectrum of the rows that
:func:`inputs.check_rows` draws is compared with the float64 reference,
and the inverse of that spectrum (a round trip) with the input.
"""

from __future__ import annotations

import math
import time

import numpy as np

import inputs
import reference
from yardstick import least_bytes


def problems(config: dict, traffic: dict) -> list:
    """The cell's problems in the order the window runs them: each of the
    mix's (extents, batch) in each of the config's kinds."""
    from repro.core.client import Problem

    return [Problem(inputs.parse_extents(extents), kind, config["precision"],
                    batch=int(batch))
            for extents, batch in traffic["problems"]
            for kind in config["kinds"]]


def make_input(seed: int, index: int, problem):
    """Problem ``index``'s input, made on the device from the seed."""
    return inputs.make(inputs.key(seed, index),
                       (problem.batch, *problem.extents),
                       problem.complex_input)


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 log):
        from repro.core.client import Context
        from repro.core.clients import dist_fft, jax_fft  # noqa: F401
        from repro.core.plan import PlanRigor
        from repro.core.registry import get_client
        from repro.core.suite import Session

        self.seed, self.log = seed, log
        self.limits = config["limits"]
        self.context = Context()
        self.context.create()
        self.session = Session(context=self.context)
        cls = get_client(config["client"])
        rigor = PlanRigor(config["rigor"])
        self.clients = []
        for j, problem in enumerate(problems(config, traffic)):
            c = cls(problem, self.context, rigor=rigor,
                    plan_cache=self.session.plan_cache)
            c.allocate()
            c.init_forward()
            c.init_inverse()
            c.upload(make_input(seed, j, problem))
            c.execute_forward()
            c.execute_inverse()
            log(f"problem {problem.signature()} "
                f"plan={c.plan.candidate.key()} source={c.plan.source}")
            self.clients.append(c)
        self.pass_bytes = sum(2 * least_bytes(c.problem.extents,
                                              c.problem.batch,
                                              not c.problem.complex_input)
                              for c in self.clients)

    # --- the window ---------------------------------------------------------
    def window(self, seconds: float, span) -> dict:
        passes = 0
        t0 = time.perf_counter()
        while True:
            for c in self.clients:
                with span("execute_forward"):
                    c.execute_forward()
                with span("execute_inverse"):
                    c.execute_inverse()
            passes += 1
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        transforms = passes * 2 * len(self.clients)
        return {"window_s": elapsed, "attempted": transforms, "failed": 0,
                "metrics": {"fft_ms": elapsed * 1e3 / transforms},
                "transforms": transforms, "passes": passes,
                "least_bytes": passes * self.pass_bytes}

    # --- the check ----------------------------------------------------------
    def check(self) -> list[tuple[str, float, float]]:
        worst = {"fwd_rel_l2": 0.0, "inv_rel_l2": 0.0}
        for j, c in enumerate(self.clients):
            p = c.problem
            real = not p.complex_input
            x = make_input(self.seed, j, p)
            rows = inputs.check_rows(self.seed, j, p.batch,
                                     math.prod(p.extents))
            c.upload(x)
            c.execute_forward()
            # the spectrum the client's forward executable wrote
            got = np.asarray(c._spec[rows])
            c.execute_inverse()
            back = np.asarray(c._buf[rows])
            xr = np.asarray(x[rows])
            del x
            fwd = float(reference.rel_l2_rows(
                got, reference.forward(xr, p.rank, real)).max())
            inv = float(reference.rel_l2_rows(back, xr).max())
            self.log(f"check {p.signature()} rows={len(rows)} "
                     f"fwd_rel_l2={fwd!r} inv_rel_l2={inv!r}")
            worst["fwd_rel_l2"] = max(worst["fwd_rel_l2"], fwd)
            worst["inv_rel_l2"] = max(worst["inv_rel_l2"], inv)
        return [(k, v, self.limits[k]) for k, v in worst.items()]

    def close(self) -> None:
        for c in self.clients:
            c.destroy()
        self.clients = []
