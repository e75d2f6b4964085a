"""CNN cells: a closed loop of batches through the system's jitted forward
(``repro.models.cnn.hybrid_forward`` on the Pallas conv kernel), each step
ended by ``block_until_ready``.

Set-up makes the weights and a pool of distinct input batches on the
device from the seed, in one jitted call each, and compiles and warms the
one forward shape the window uses. After the window, a seeded sample of
the steps' outputs is compared with the plain float32 reference of the
same weights and inputs.
"""
from __future__ import annotations

import contextlib
import sys
import time

import numpy as np

from chipbench import flops, harness
from chipbench.reference import cnn as ref


class Cell:
    def __init__(self, spec: harness.CellSpec, seed: int, devices, peak):
        self.spec, self.seed, self.peak = spec, seed, peak
        self.cfg, self.tr = spec.config, spec.traffic
        self.h, self.w = self.tr["height"], self.tr["width"]
        self.batch = self.tr["batch"]
        self.sample = harness.Reservoir(
            self.tr["sample_steps"],
            np.random.default_rng(harness.seed_words(seed, 3)))

    def program_step(self, net, plan):
        """The timed path: the system's jitted forward."""
        import jax
        from repro.models.cnn import hybrid_forward
        use_pallas = self.cfg["use_pallas"]
        return jax.jit(lambda p, x: hybrid_forward(p, net, x, plan,
                                                   use_pallas=use_pallas))

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.core import netinfo
        from repro.models.cnn import HybridPlan

        net = getattr(netinfo, self.cfg["name"])(self.h, self.w)
        got = [(l.c, l.k, l.r, l.s, l.h, l.w) for l in net.layers
               if l.kind != "pool"]
        want = flops.vgg_convs(self.cfg, self.h, self.w)
        if got != want:
            raise ValueError(f"the program's {self.cfg['name']} convs {got} "
                             f"differ from the configuration's {want}")
        dtype = getattr(jnp, self.cfg["dtype"])
        self.weights = ref.init_weights(
            jax.random.key(harness.seed_words(self.seed, 1)),
            self.cfg["convs"], dtype)
        it = iter(self.weights)
        self.params = [None if l.kind == "pool" else next(it)
                       for l in net.layers]
        n_in = self.tr["input_batches"]
        shape = (self.batch, 3, self.h, self.w)
        self.inputs = jax.jit(lambda k: [
            jax.random.normal(kk, shape, dtype)
            for kk in jax.random.split(k, n_in)])(
                jax.random.key(harness.seed_words(self.seed, 2)))
        step = self.program_step(net, HybridPlan(**self.cfg["plan"]))
        self.step = step.lower(self.params, self.inputs[0]).compile()
        m = self.step.memory_analysis()
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)
        print(f"compiled forward: {need} bytes (arguments, output and "
              f"temporaries) of the chip's {self.peak['hbm_bytes']:.0f}",
              file=sys.stderr, flush=True)
        if need > self.peak["hbm_bytes"]:
            raise ValueError(f"batch {self.batch} needs {need} bytes, more "
                             f"than the chip's {self.peak['hbm_bytes']:.0f}")
        self.step(self.params, self.inputs[0]).block_until_ready()

    def run_window(self, seconds: float, annotate) -> None:
        """``annotate`` is the profiler's annotation in a traced run."""
        annotate = annotate or (lambda _name: contextlib.nullcontext())
        n_in = len(self.inputs)
        steps = 0
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            with annotate("bench.step"):
                y = self.step(self.params, self.inputs[steps % n_in])
                y.block_until_ready()
            self.sample.offer((steps, y))
            steps += 1
            if time.perf_counter() >= deadline:
                break
        self.elapsed = time.perf_counter() - t0
        self.steps = steps

    def end_to_end(self) -> dict:
        return {"images_per_s": harness.rate(self.steps * self.batch,
                                             self.elapsed)}

    def context(self) -> dict:
        return {"images_per_s": self.end_to_end()["images_per_s"],
                "flops_per_image": flops.vgg_flops_per_image(
                    self.cfg, self.h, self.w),
                "roofline_s": self.steps * flops.vgg_roofline_s(
                    self.cfg, self.h, self.w, self.batch,
                    self.peak["bf16_flops"], self.peak["hbm_bytes_per_s"])}

    def attempted_failed(self) -> tuple[int, int]:
        return self.steps * self.batch, 0

    def release(self) -> None:
        del self.step, self.params

    def _worst(self, produce) -> tuple[float, int]:
        """Worst per-image relative L2 error against the reference, and
        the count of non-finite outputs, over every image of the sampled
        steps; ``produce(x_rows, y_rows)`` gives the rows compared."""
        import jax
        import jax.numpy as jnp
        rb = self.tr["reference_rows"]
        pools = set(self.cfg["pools_after"])
        fwd = jax.jit(lambda w, x: ref.forward(w, pools, x))
        worst, nonfinite = 0.0, 0
        for i, y in self.sample.items:
            x = self.inputs[i % len(self.inputs)]
            for r0 in range(0, self.batch, rb):
                xr = x[r0:r0 + rb]
                want = fwd(self.weights, xr)
                got = produce(xr, y[r0:r0 + rb]).astype(jnp.float32)
                err = jnp.sqrt(jnp.sum((got - want) ** 2, axis=(1, 2, 3))
                               / jnp.sum(want ** 2, axis=(1, 2, 3)))
                nonfinite += int(jnp.sum(~jnp.isfinite(got)))
                e = float(jnp.max(err))
                if not e <= worst:       # a NaN fails too
                    worst = e
        return worst, nonfinite

    def checks(self) -> list[harness.Check]:
        """Every image of the sampled steps against the reference."""
        worst, nonfinite = self._worst(lambda x, y: y)
        lim = self.tr["limits"]
        return [harness.Check("max_rel_l2", worst, lim["max_rel_l2"]),
                harness.Check("nonfinite", float(nonfinite),
                              lim["nonfinite"])]

    def control(self) -> dict:
        """The compared numbers with the reference in float8 in the
        program's place, on the same sampled inputs."""
        import jax
        pools = set(self.cfg["pools_after"])
        fp8 = jax.jit(lambda w, x: ref.forward(w, pools, x, control=True))
        worst, nonfinite = self._worst(lambda x, y: fp8(self.weights, x))
        return {"max_rel_l2": worst, "nonfinite": float(nonfinite)}
