"""Unit ``fit``: a fresh ``FalkonRegressor(...).fit(x, y, center_set=...)``
on the centers that set-up sampled; nothing else is carried between fits.

The traffic mix that names this unit gives ``metric``, the end-to-end
metric that reports the window's seconds per fit. The configuration gives
the kernel (``make_kernel`` arguments), the sampler (a class of
``repro.api`` and its arguments; a configuration's ``centers``, where
given, is the sampler's ``m``) and the solver (``FitConfig`` arguments).

The check compares ``pred_gap``: the held-out predictions of the last
timed fit's alpha against those of the plain reference's own FALKON fit on
the same centers, both predicted by the reference at highest precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import reference


def rel_gap(got, want) -> float:
    """||got - want|| / ||want||, in float64 on the host."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


class Unit:
    def __init__(self, api, cell, data, seed: int):
        self.api, self.cfg, self.mix, self.seed = api, cell.config, cell.traffic, seed
        self.x, self.y, self.xte, self.yte = data
        self.kernel = api.make_kernel(**self.cfg["kernel"])
        args = {k: v for k, v in self.cfg["sampler"].items() if k != "class"}
        if "centers" in self.cfg:
            args["m"] = self.cfg["centers"]
        self.sampler = getattr(api, self.cfg["sampler"]["class"])(**args)
        self.backend = None  # the platform's choice; a control may set another
        self.centers = None
        self.last = None

    def setup(self):
        """The center set every fit of the run uses, from the run's seed."""
        self.centers = self.sampler.sample(jax.random.PRNGKey(self.seed), self.x, self.kernel)
        jax.block_until_ready(self.centers)

    def run(self):
        cfg = self.api.FitConfig(**self.cfg["fit"], backend=self.backend)
        est = self.api.FalkonRegressor(kernel=self.kernel, sampler=self.sampler, config=cfg)
        est.fit(self.x, self.y, center_set=self.centers)
        jax.block_until_ready(est.model_.alpha)
        self.last = est

    def end_to_end(self, window_s: float, units: int) -> dict:
        return {self.mix["metric"]: window_s / units}

    def shapes(self) -> dict:
        n, d = self.x.shape
        return {"n": n, "d": d, "m": int(self.last.centers_.shape[0]),
                "iters": self.cfg["fit"]["iters"]}

    def program_outputs(self) -> dict:
        """The last fit's alpha, with the centers and weights it was fitted on."""
        est, self.last = self.last, None
        return {"alpha": np.asarray(est.model_.alpha), "centers": est.centers_,
                "a_diag": est.a_diag_}

    def reference_outputs(self, prog: dict) -> dict:
        """The plain reference's FALKON fit on the same centers."""
        c = self.cfg
        with jax.default_matmul_precision("highest"):
            alpha = reference.falkon(self.x, self.y, prog["centers"], prog["a_diag"],
                                     c["kernel"]["sigma"], c["fit"]["lam"], c["fit"]["iters"])
        return dict(prog, alpha=np.asarray(alpha))

    def compare(self, got: dict, want: dict) -> dict:
        sigma = jnp.float32(self.cfg["kernel"]["sigma"])
        z = want["centers"]
        with jax.default_matmul_precision("highest"):
            p_got = reference.knm_matvec(self.xte, z, sigma, jnp.asarray(got["alpha"]))
            p_want = reference.knm_matvec(self.xte, z, sigma, jnp.asarray(want["alpha"]))
        return {"pred_gap": rel_gap(p_got, p_want)}
