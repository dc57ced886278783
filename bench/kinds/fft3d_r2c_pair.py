"""heFFTe ``speed3d_r2c``: forward r2c 3D FFT, then the inverse, repeated.

One step is ``s = fwd(x); x = inv(*s)``: the inverse, fully scaled, is
written back over the input, as speed3d's run does, so each step's answer
is the next step's input. The program's default plan serves both
directions (``make_fft3d(mesh, n, real=True)``, no spec pinned).

Correctness, after the window:

* ``fwd_err`` — the spectrum of one step of the window, drawn from the
  seed with equal chances for every step (reservoir sampling), against a
  float64 SciPy ``rfftn`` of that step's input: relative L2 error over the
  whole half spectrum, padding rows included;
* ``roundtrip_err`` — the worst relative L2 gap between a step's output
  and its input, whose exact round trip is the identity, over the sampled
  step and the last.
"""

from __future__ import annotations

import numpy as np

from bench.reference import bf16, rel_l2
from bench.reference import fft3d_pair as ref


class Case:
    def __init__(self, config, traffic, mesh, seed):
        if config["input"]["distribution"] != "uniform":
            raise ValueError(f"{config['name']}: only uniform input is drawn")
        self.config, self.mesh = config, mesh
        self.n = int(traffic["n"])
        self.seed = int(seed)
        self.pick = np.random.default_rng([self.seed, 1])

    def setup(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding

        from repro.core.fft3d import make_fft3d

        n = self.n
        self.fwd, self.inv, plan = make_fft3d(self.mesh, n, real=True)
        sharding = NamedSharding(self.mesh, plan.grid.pencil_spec())
        dist = self.config["input"]
        key = jax.random.key(int(np.random.default_rng(self.seed)
                                 .integers(2 ** 32)))
        gen = jax.jit(lambda k: jax.random.uniform(
            k, (n, n, n), jnp.dtype(self.config["dtype"]),
            dist["low"], dist["high"]), out_shardings=sharding)
        self.x0 = gen(key)
        for _ in range(2):            # compile, then one warm call
            spectrum = self.fwd(self.x0)
            jax.block_until_ready(self.inv(*spectrum))
        self.spectrum_shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                     sharding=a.sharding)
                                for a in spectrum]
        self.x = self.x0
        self.count = 0

    def step(self):
        s = self.fwd(self.x)
        y = self.inv(*s)
        self.count += 1
        if self.pick.random() * self.count < 1:     # keep with chance 1/count
            self.sampled = (self.x, s, y)
        self.last = (self.x, y)
        self.x = y
        return y

    def programs(self):
        """The compiled programs the window runs, with arguments to lower
        them by: their HLO names the trace's ops."""
        return [(self.fwd, (self.x0,)), (self.inv, self.spectrum_shapes)]

    def collect(self):
        """Host copies of what the comparison needs; frees the program's
        device state (the round-trip gaps are taken on the device first)."""
        import jax
        import jax.numpy as jnp

        x_prev, s, y = self.sampled
        gap = jax.jit(lambda a, b: jnp.linalg.norm(b - a) / jnp.linalg.norm(a))
        roundtrip = max(float(gap(a, b)) for a, b in ((x_prev, y), self.last))
        host = {"x_prev": np.asarray(x_prev),
                "spectrum": tuple(np.asarray(a) for a in s),
                "roundtrip": roundtrip}
        del self.x, self.x0, self.last, self.sampled, x_prev, s, y
        return host

    def compare(self, host):
        """The compared numbers: ``{name: value}``; keeps the reference
        spectrum in ``host`` for :meth:`control`."""
        host["want"] = ref.rfft3(host["x_prev"])
        return {"fwd_err": ref.spectrum_rel_l2(*host["spectrum"], host["want"]),
                "roundtrip_err": host["roundtrip"]}

    def control(self, host):
        """The same numbers with the reference in bfloat16 in the
        program's place."""
        x = host["x_prev"]
        low = ref.rfft3(x, rnd=bf16)
        fwd = ref.spectrum_rel_l2(low.real, low.imag, host["want"])
        back = ref.irfft3(low, self.n, rnd=bf16)
        return {"fwd_err": fwd, "roundtrip_err": rel_l2(back, x)}
