"""Batched transform traffic: back-to-back calls of one plan executor.

The operand, ``(batch, n)`` complex, is made on the device from the seed in
one jitted call (sharded over the ``fft`` mesh axis on several chips). The
window dispatches forward calls back to back, with at most two in flight,
and ends with ``block_until_ready`` on the last.

Traffic keys: ``n``, ``batch``; for an ABFT configuration ``seu_period``
(one call in that many carries an enabled SEU descriptor) and ``eps`` (the
range of the error's magnitude); for a transposed-order mesh
``transposed_split`` (``[n1, n2]``: ``y[k1*n2 + k2] = X[k1 + n1*k2]``);
``limits``: the correctness limits.

The check compares the last call's output with float64 NumPy: every row on
one chip, and on several chips the sum of all rows under random signs
(linearity makes that the transform of the signed sum of the inputs, so any
wrong row shows). Each SEU must be flagged in exactly one group, located at
its signal, and corrected, and its row must match; a clean call must flag
nothing.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import reference, schedule
from benchmarks.chip.trace import PLAIN, WINDOW

# calls per second the pre-made SEU descriptors cover before they repeat
MAX_CALLS_PER_S = 1000


def make_operand(key, shape, sharding=None):
    """Standard complex normal ``shape`` complex64, made on the device."""
    def make(k):
        kr, ki = jax.random.split(k)
        return jax.lax.complex(jax.random.normal(kr, shape, jnp.float32),
                               jax.random.normal(ki, shape, jnp.float32))
    return jax.jit(make, out_shardings=sharding)(key)


def fetch(a) -> np.ndarray:
    """A complex device array on the host as complex128 (real and imaginary
    parts come over apart: several times faster than complex64)."""
    return np.asarray(jnp.real(a)) + 1j * np.asarray(jnp.imag(a))


@jax.jit
def _take_row(y, i):
    return jax.lax.dynamic_index_in_dim(y, i, 0, keepdims=False)


@jax.jit
def _signed_sum(c, a):
    return jnp.sum(c[:, None].astype(a.dtype) * a, axis=0)


class Cell:
    def __init__(self, ctx):
        from repro.core.fft.api import FFTSpec, FTConfig, plan

        cfg, tr = ctx.config, ctx.traffic
        self.ctx = ctx
        self.b, self.n = int(tr["batch"]), int(tr["n"])
        self.limits = tr["limits"]
        mesh = sharding = None
        if ctx.chips > 1:
            from jax.sharding import NamedSharding, PartitionSpec
            from repro.launch.mesh import make_fft_mesh
            mesh = make_fft_mesh(ctx.chips)
            sharding = NamedSharding(mesh, PartitionSpec(None, "fft"))
        self.ft = "ft" in cfg
        spec = FFTSpec(shape=(self.b, self.n), dtype=cfg["dtype"], mesh=mesh,
                       natural_order=cfg.get("natural_order", True),
                       chunks=cfg.get("chunks", 1),
                       ft=FTConfig(**cfg["ft"]) if self.ft else None)
        self.plan = plan(spec)
        self.x = make_operand(schedule.jax_key(ctx.seed), (self.b, self.n),
                              sharding)
        self.split = tr.get("transposed_split")
        self.itemsize = np.dtype(cfg["dtype"]).itemsize
        self.last = None
        self.seus = []          # (call, descriptor, flagged, location, row)
        self.corrected = []     # every call's correction count (device)
        if self.ft:
            self._setup_seus(tr)
        self._warm()

    # -- set-up -------------------------------------------------------------

    def _setup_seus(self, tr):
        from repro.core.fft.plan import make_plan
        gen = schedule.rng(self.ctx.seed, 1)
        self.period = int(tr["seu_period"])
        self.phase = int(gen.integers(0, self.period))
        self.bs = min(make_plan(self.n, batch=self.b).bs, self.b)
        count = int(self.ctx.seconds * MAX_CALLS_PER_S) // self.period + 2
        descs = schedule.seu_descriptors(
            count, tiles=self.b // self.bs, bs=self.bs, n=self.n,
            eps=tuple(tr["eps"]), gen=gen)
        self.descs = descs
        self.inj = [jax.device_put(d) for d in descs]
        self.sig = [jnp.int32(int(d[0]) * self.bs + int(d[1]))
                    for d in descs]
        self.off = jax.device_put(np.zeros(6, np.float32))

    def _call(self, i):
        if not self.ft:
            return self.plan.fft(self.x)
        k, r = divmod(i, self.period)
        k %= len(self.inj)
        if r != self.phase:
            out = self.plan.ft_fft(self.x, inject=self.off)
        else:
            out = self.plan.ft_fft(self.x, inject=self.inj[k])
            self.seus.append((i, self.descs[k], out.flagged, out.location,
                              _take_row(out.y, self.sig[k])))
        self.corrected.append(out.corrected)
        return out

    def _warm(self):
        jax.block_until_ready(self.plan.fft(self.x) if not self.ft else
                              self.plan.ft_fft(self.x, inject=self.off))
        if self.ft:
            out = self.plan.ft_fft(self.x, inject=self.inj[0])
            jax.block_until_ready(_take_row(out.y, self.sig[0]))
            if self.ctx.trace:      # the plain executor abft_overhead reads
                from repro.core.fft.api import FFTSpec, plan
                self.plain = plan(FFTSpec(shape=(self.b, self.n),
                                          dtype=self.plan.spec.dtype))
                jax.block_until_ready(self.plain.fft(self.x))
        jax.block_until_ready(self._call(0))
        self.seus.clear()
        self.corrected.clear()

    # -- window -------------------------------------------------------------

    def _loop(self, call, seconds):
        calls, prev = 0, None
        t0 = time.perf_counter()
        while True:
            out = call(calls)
            if prev is not None:
                jax.block_until_ready(prev)
            prev, calls = out, calls + 1
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(prev)
        return calls, time.perf_counter() - t0, prev

    def window(self, seconds: float) -> dict:
        with jax.profiler.TraceAnnotation(WINDOW):
            calls, wall, out = self._loop(self._call, seconds)
        self.last = out.y if self.ft else out
        self.last_seu = None
        if self.seus and self.seus[-1][0] == calls - 1:
            d = self.seus[-1][1]
            self.last_seu = int(d[0]) * self.bs + int(d[1])
        record = {"calls": calls, "wall_s": wall, "attempted": calls,
                  "failed": 0, "b": self.b, "n": self.n,
                  "itemsize": self.itemsize, "chips": self.ctx.chips,
                  "e2e": {"throughput": calls * self.b * self.n / wall / 1e9}}
        if self.ft and self.ctx.trace:
            plain_calls = max(1, min(calls, 64))
            with jax.profiler.TraceAnnotation(PLAIN):
                pc, _, _ = self._loop(lambda i: self.plain.fft(self.x),
                                      seconds * plain_calls / calls)
            record["plain"], record["plain_calls"] = True, pc
        if self.ft:
            self.verdicts = self._verdicts()
            record["failed"] = (self.verdicts["seu_missed"]
                                + self.verdicts["false_flags"])
            record["seus"] = len(self.seus)
        return record

    def _verdicts(self) -> dict:
        corrected = np.asarray(jax.device_get(self.corrected))
        seu_calls = {s[0] for s in self.seus}
        clean = [c for i, c in enumerate(corrected) if i not in seu_calls]
        missed = 0
        for i, d, flagged, location, _ in self.seus:
            flagged = np.asarray(flagged)
            sig = int(d[0]) * self.bs + int(d[1])
            ok = (flagged.sum() == 1 and corrected[i] == 1
                  and int(np.asarray(location)[np.argmax(flagged)]) == sig)
            missed += not ok
        return {"seu_missed": missed,
                "false_flags": int(np.count_nonzero(clean))}

    # -- check --------------------------------------------------------------

    def check(self, control: dict | None = None) -> list[dict]:
        lim = self.limits
        if self.ctx.chips > 1:
            return [{"name": "sum_rel_err", "value": self._signed_sum_err(
                control), "limit": lim["sum_rel_err"]}]
        x = fetch(self.x)
        y = fetch(reference.lowp_fft(self.x, **control) if control
                  else self.last)
        keep = np.ones(self.b, bool)
        if self.ft and not control and self.last_seu is not None:
            keep[self.last_seu] = False   # seu_row_rel_err reads that row
        err = 0.0
        for i in range(0, self.b, 8192):
            rows = np.flatnonzero(keep[i:i + 8192]) + i
            err = max(err, reference.rel_err(y[rows],
                                             reference.fft64(x[rows])))
        checks = [{"name": "rel_err", "value": err,
                   "limit": lim["rel_err"]}]
        if not self.ft or control:
            return checks
        seu_err = 0.0
        for _, d, _, _, row in self.seus:
            sig = int(d[0]) * self.bs + int(d[1])
            seu_err = max(seu_err, reference.rel_err(
                np.asarray(row)[None], reference.fft64(x[sig:sig + 1])))
        checks += [
            {"name": "seu_row_rel_err", "value": seu_err,
             "limit": lim["seu_row_rel_err"]},
            {"name": "seu_missed", "value": self.verdicts["seu_missed"],
             "limit": 0},
            {"name": "false_flags", "value": self.verdicts["false_flags"],
             "limit": 0},
            {"name": "no_seu_injected", "value": int(not self.seus),
             "limit": 0},
        ]
        return checks

    def _signed_sum_err(self, control) -> float:
        c = schedule.rng(self.ctx.seed, 2).choice([-1.0, 1.0], size=self.b)
        cd = jnp.asarray(c, jnp.float32)
        xs = _signed_sum(cd, self.x)
        if control:
            ys = None
            for r in range(self.b):
                row = reference.lowp_fft(
                    jax.device_put(_take_row(self.x, jnp.int32(r)),
                                   self.ctx.devices[0])[None], **control)[0]
                # a Python float keeps the product complex64 under x64
                ys = row * float(c[r]) if ys is None \
                    else ys + row * float(c[r])
            got = fetch(ys)
        else:
            n1, n2 = self.split
            got = fetch(_signed_sum(cd, self.last))
            got = got.reshape(n1, n2).T.reshape(self.n)
        return reference.rel_err(got[None], reference.fft64(fetch(xs)[None]))

    def close(self):
        self.last = self.x = None
        self.seus.clear()
        self.corrected.clear()
