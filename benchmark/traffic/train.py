"""The training kind: one general generator of batches, read from the
cell's data file, and the driver of `fit`.

A cell's file gives `batch`, `input` (`token_ids`: `seq_len`, Zipf
exponent `zipf_a` over the configuration's vocabulary), `pool_batches`,
and `limits`.  The same seed gives the same
pool of batches in the same order; every row differs from every other.

What is timed is `net.fit(iterator)`: the iterator is the benchmark's
own `DataSetIterator`, so the batch's way to the device (and for tokens
the one-hot labels the program asks for, built on the device) is inside
the window as it is for a user; every step ends with the loss readback
`fit` makes.  The same net, compiled step and state are first driven
through three steps that the plain reference follows, then warmed, then
handed to the window.
"""

from __future__ import annotations

import time

import numpy as np

import correct
import harness
from harness import say

FOLLOWED = 3          # steps the reference follows
WARM_STEPS = 2        # further steps before the window opens


# --------------------------------------------------------------- generator
def make_pool(cell, cfg, seed):
    """Host pool of batches from the seed: int32 [pool, B, T+1] token
    ids (inputs and next-token labels overlap by T-1)."""
    rng = np.random.default_rng(int(seed))
    spec, B, n = cell["input"], cell["batch"], cell["pool_batches"]
    if spec["kind"] != "token_ids":
        raise ValueError(f"unknown input kind {spec['kind']!r}")
    V, T = cfg["vocab_size"], cell["seq_len"]
    p = 1.0 / np.arange(1, V + 1) ** spec["zipf_a"]
    cdf = np.cumsum(p / p.sum())
    ids = np.searchsorted(cdf, rng.random((n, B, T + 1)))
    return np.minimum(ids, V - 1).astype(np.int32)


def make_iterator(cell, cfg, pool, tracer, devs=None):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import DataSetIterator

    V = cfg["vocab_size"]
    one_hot = jax.jit(lambda i: jax.nn.one_hot(i, V, dtype=jnp.float32))

    def batch(i):
        ids = pool[i % len(pool)]
        return (jax.device_put(ids[:, :-1]),
                one_hot(jax.device_put(ids[:, 1:])))

    class Feed(DataSetIterator):
        """Yields batches `start`, `start`+1, ... until `stop()` says so."""

        def __init__(self):
            self.next_index = 0
            self.stop = lambda served: True
            self.open_step = None
            self.bytes_seen = []    # live bytes with each new batch on the device

        def __iter__(self):
            served = 0
            while not self.stop(served):
                with tracer.span("bench/etl"):
                    x, y = batch(self.next_index)
                if devs:
                    self.bytes_seen.append(harness.bytes_in_use(devs))
                self.next_index += 1
                served += 1
                self.open_step = tracer.span("bench/step")
                self.open_step.__enter__()
                yield DataSet(x, y)

        def close_step(self):
            if self.open_step is not None:
                self.open_step.__exit__(None, None, None)
                self.open_step = None

    return Feed()


# ------------------------------------------------------------------ driver
class Trainer:
    """The net of one cell with its compiled step, state, feed and log:
    ONE object, driven from the seed through the steps the reference
    follows and then handed to the window."""

    def __init__(self, cell, cfg, tracer, hooks=None, devs=None):
        import jax

        from deeplearning4j_tpu.optimize.listeners import TrainingListener

        self.cell, self.cfg, self.tracer, self.devs = cell, cfg, tracer, devs
        self.common = harness.load_module("reference", "common")
        self.model = harness.load_module("models", cell["config"])
        self.ref = harness.load_module("reference", cell["config"])
        self.net = self.model.build(cfg)
        if hooks and "net" in hooks:
            hooks["net"](self.net)
        model, ref, common = self.model, self.ref, self.common
        stacked = getattr(ref, "STACKED", ())

        def make(words):
            _, state, upd = self.net._init_trees(0)
            p = model.to_program(ref.init_params(cfg, common.key_of(words)),
                                 cfg)
            return p, state, upd

        self._make = jax.jit(make)
        self.stacked = stacked
        self._first_gradient = jax.jit(
            lambda upd: model.first_gradient(upd, cfg))
        self._change_norms = jax.jit(lambda p, w: common.leaf_norms(
            jax.tree_util.tree_map(
                lambda a, b: a - b, model.to_reference(p, cfg),
                ref.init_params(cfg, common.key_of(w))), stacked))
        trainer = self

        class Log(TrainingListener):
            def iteration_done(self, model_, iteration, epoch, score, **info):
                trainer._step_done(model_, iteration, score, info)

        self.net.set_listeners(Log())

    def install(self, seed):
        """Weights from the seed in ONE jitted call on the device, under
        the reference's own initialisation, laid out as the program
        holds them; optimizer and layer state as the program starts them."""
        net = self.net
        self.words = self.common.seed_words(seed)
        self.release()
        net.params, net.net_state, net.updater_state = self._make(self.words)
        net._initialized = True
        net.iteration_count = net.epoch_count = 0
        self.pool = make_pool(self.cell, self.cfg, seed)
        self.feed = make_iterator(self.cell, self.cfg, self.pool, self.tracer,
                                  self.devs)
        self.ends, self.etl_ms = [], []
        self.first_gradient = None
        self.prog = {"losses": []}

    def _step_done(self, net, iteration, score, info):
        import jax

        self.feed.close_step()
        self.ends.append(time.monotonic())
        self.etl_ms.append(float(info.get("etl_ms") or 0.0))
        if iteration < FOLLOWED:
            self.prog["losses"].append(float(score))
            if iteration == 0:
                # the first gradient as the optimizer got it: its norms by
                # leaf now, the whole of it to the host (not left on the
                # device, where it would count in the window's peak)
                g = self._first_gradient(net.updater_state)
                self.prog["grad_norm"] = self.common.leaf_norms(
                    g, self.stacked)
                self.first_gradient = jax.device_get(g)
                del g
            if iteration == FOLLOWED - 1:
                self.prog["change_norm"] = self._change_norms(net.params,
                                                              self.words)
        if self.tracer.due():
            self.tracer.stop()

    def follow(self):
        """The first steps, through the window's own call and feed."""
        self.feed.stop = lambda served: served >= FOLLOWED
        self.net.fit(self.feed, epochs=1)
        for k in ("grad_norm", "change_norm"):
            self.prog[k] = self.common.flatten_norms(self.prog[k])
        return self.prog

    def steps(self, stop):
        """Go on with `fit` until `stop(batches served)`; -> the times at
        which the steps ended."""
        n0 = len(self.ends)
        self.feed.stop = stop
        self.net.fit(self.feed, epochs=1)
        return self.ends[n0:]

    def release(self):
        """Free the program's state on the device."""
        net = self.net
        if net.params:
            self.common.free(net.params, net.updater_state, net.net_state)
        net.params, net.updater_state, net.net_state = {}, {}, {}
        self.feed = None

    def reference(self, seed, mode="f32", rows=None, other=None, keep=False):
        """The reference's readings; `other`: a first gradient (the
        program's, or a control's) whose direction it is compared with;
        `keep`: also hand back its own first gradient."""
        return self.ref.train_readings(
            self.cfg, self.cell, seed,
            [self.pool[i] for i in range(FOLLOWED)],
            mode=mode, rows=rows, other_first_gradient=other,
            keep_first_gradient=keep)


def run(bench, cell, cfg, args, devs, counters, tracer, hooks=None):
    """One run of a training cell.  Returns the pieces of the result
    line.  `hooks` is for tests: {"net": f(net)} may break the timed path
    after it is built."""
    work = harness.load_module("work", cell["config"])
    tr = Trainer(cell, cfg, tracer, hooks, devs)
    tr.install(args.seed)
    say(f"built {cell['config']}: {tr.net.num_params():,} parameters, "
        f"{tr.net.dtype.name}, batch {cell['batch']}")
    prog = tr.follow()
    tr.steps(lambda served: served >= WARM_STEPS)
    say(f"followed {FOLLOWED} steps and warmed {WARM_STEPS}: losses "
        f"{prog['losses']}")

    # ---- the window
    compiles0 = counters.compiles()
    cache_setup = (counters.cache_requests, counters.cache_hits)
    n0, b0 = len(tr.etl_ms), len(tr.feed.bytes_seen)
    tracer.start()
    t0 = time.monotonic()
    # a traced run measures the traced window and no more: closing the
    # profile stalls the host for seconds, which is no part of any step
    deadline = t0 + (tracer.seconds if tracer.on else args.seconds)
    ends = tr.steps(lambda served: time.monotonic() >= deadline
                    or (tracer.on and not tracer.running))
    tracer.stop()
    steps = len(ends)
    if steps == 0:
        raise RuntimeError("no step ended in the window")
    window_s = ends[-1] - t0
    compiles_in_window = counters.compiles() - compiles0
    setup_s = t0 - harness.T0
    traced_steps = (sum(1 for t in ends if t <= tracer.t_stop)
                    if tracer.t_stop else 0)
    device = harness.device_line(devs, tr.feed.bytes_seen[b0:])
    say(f"window: {steps} steps in {window_s:.3f}s, "
        f"{compiles_in_window} compiles inside it")
    # where a run that reads far off lost its time: runs differ by a few
    # steps that stall some 100 ms, not by the median step (PERF.md)
    each = 1e3 * np.diff([t0] + ends)
    slow = np.argsort(each)[::-1][:3]
    say(f"steps: median {np.median(each):.2f} ms, 90th percentile "
        f"{np.percentile(each, 90):.2f}, longest "
        f"{[(int(i), round(float(each[i]), 1)) for i in slow]} (index, ms); "
        f"{1e-3 * float((each - np.median(each)).clip(0).sum()):.3f} s of "
        f"the window above the median step")

    # ---- free the program's state, then the reference follows
    etl_ms = tr.etl_ms[n0:]
    tr.release()
    t_ref = time.monotonic()
    refr = tr.reference(args.seed, other=tr.first_gradient)
    say(f"reference followed {FOLLOWED} steps in "
        f"{time.monotonic() - t_ref:.1f}s: losses {refr['losses']}")
    numbers = correct.training_numbers(prog, refr)
    ok, compared = correct.judge(numbers, cell["limits"])
    compared["compiles_in_window"] = {"value": compiles_in_window, "limit": 0,
                                      "ok": compiles_in_window == 0}
    ok = ok and compiles_in_window == 0

    ctx = {"cell": cell, "cfg": cfg, "work": work, "chips": len(devs),
           "peaks": harness.peaks_of(devs, args), "trace": tracer.reduce(),
           "window_s": window_s, "units": steps, "traced_units": traced_steps,
           "etl_ms": etl_ms, "compiles_in_window": compiles_in_window,
           "cache_requests": cache_setup[0], "cache_hits": cache_setup[1]}
    return {
        "correct": bool(ok), "attempted": steps, "failed": 0,
        "end_to_end": {"train_step_ms": 1e3 * window_s / steps,
                       "setup_s": setup_s},
        "device": device, "ctx": ctx, "compared": compared,
    }
