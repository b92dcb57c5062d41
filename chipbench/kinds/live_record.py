"""The live-recording generator: a kernel author recording a kernel's
whole search space on the chip with ``Tuner.record`` (the live runner:
each configuration compiled, run once, then timed ``repeats`` times).

Configuration (``configs/<name>.json``, ``"kind": "live_record"``):

  kernel    the registered kernel; its module's entry point of that name
            is the program whose outputs the check compares
  problem   its problem sizes; ``--seed`` adds the inputs' key
  repeats   timed runs per configuration
  space     the tunables the recording has to cover, each with its
            values, and ``divides``: the problem size each of the listed
            tunables has to divide

Traffic (``traffic/<name>.json``):

  check     ``{"sample": k, "max_abs_error": e}``: the outputs of k
            configurations of the last pass, drawn from the seed, and of
            the one with the smallest blocks, are compared with the plain
            reference; e is the limit of the largest absolute error

The window runs pass after pass. Each pass is a fresh recording of every
valid configuration (bruteforce): its output and shards are removed first,
or it would resume, and the process's compiled programs are dropped
(``jax.clear_caches``), so each pass traces, lowers and loads every
program as a recording in a fresh process does. The programs come from
the persistent cache that set-up filled: per pass one kernel program per
pair of block sizes and the few small programs that make the inputs.
"""
from __future__ import annotations

import glob
import itertools
import os
import random
import re
import sys
import time

import harness

RATE = "configs_recorded_per_s"
SPAN = "chipbench.record"


def key_seed(seed: int) -> int:
    """The inputs' key from ``--seed``, which may exceed 32 bits."""
    return seed & 0x7FFFFFFF


def expected_label(device_kind: str) -> str:
    return re.sub(r"[^a-z0-9]+", "_", device_kind.lower()).strip("_")


def space_configs(config: dict) -> list:
    """Every configuration the recording has to hold, as dicts in the
    tunables' order."""
    space = config["space"]
    names = list(space["tunables"])
    divides = space.get("divides", {})
    out = []
    for values in itertools.product(*space["tunables"].values()):
        conf = dict(zip(names, values))
        if all(config["problem"][size] % conf[t] == 0
               for size, ts in divides.items() for t in ts):
            out.append(conf)
    return out


def config_id(conf: dict) -> str:
    return ",".join(str(v) for v in conf.values())


def blocks(conf: dict) -> tuple:
    """The block sizes of a configuration: what selects the program."""
    return tuple(sorted((k, v) for k, v in conf.items()
                        if k.startswith("block")))


class Generator:
    rate = RATE

    def __init__(self, cell, seed: int, log=sys.stdout):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = seed
        self.log = log
        self.kernel = self.config["kernel"]
        self.problem = dict(self.config["problem"], seed=key_seed(seed))
        self.out = os.path.join(harness.WORK, "record",
                                f"{cell.name}.json.gz")
        self.passes = 0
        self.failed = 0
        self.last = None          # the last pass's recorded cache
        self.kept: dict = {}      # (block sizes) -> last output
        self.sample: set = set()
        self.tuner = None
        self._module = None
        self._entry = None

    # ------------------------------------------------------------ interception
    def _keep_outputs(self) -> None:
        """Keep the outputs of the sampled configurations as the timed
        path makes them: the live runner calls the kernel module's entry
        point by name at each evaluation."""
        import importlib
        self._module = importlib.import_module(f"repro.kernels.{self.kernel}")
        entry = self._entry = getattr(self._module, self.kernel)
        gen = self

        def kernel(*args, **kw):
            out = entry(*args, **kw)
            key = blocks(kw)
            if key in gen.sample:
                gen.kept[key] = out
            return out
        setattr(self._module, self.kernel, kernel)

    def _restore(self) -> None:
        if self._module is not None:
            setattr(self._module, self.kernel, self._entry)
            self._module = None

    # ---------------------------------------------------------------- set-up
    def setup(self) -> None:
        from repro.api import Tuner
        programs = sorted({blocks(c) for c in space_configs(self.config)})
        rng = random.Random(self.seed)
        k = int(self.traffic["check"]["sample"])
        self.sample = set(rng.sample(programs, min(k, len(programs))))
        self.sample.add(min(programs, key=lambda b: [v for _, v in b]))
        self._keep_outputs()
        self.tuner = Tuner(seed=self.seed)
        self._pass()  # compiles every configuration, or loads it

    def _pass(self) -> int:
        for path in glob.glob(self.out[:-len(".json.gz")] + "*"):
            os.remove(path)
        import jax
        jax.clear_caches()
        run = self.tuner.record(
            self.kernel, runner="live", problem=self.problem,
            repeats=int(self.config["repeats"]), max_evals=None,
            out=self.out, bruteforce=True)
        self.last = run.cache
        return len(run.cache.results)

    # ---------------------------------------------------------------- window
    def step(self) -> int:
        """One fresh recording of the space; returns the configurations
        recorded."""
        import jax
        with jax.profiler.TraceAnnotation(f"{SPAN} pass {self.passes}"):
            n = self._pass()
        self.passes += 1
        self.failed += sum(r.status != "ok"
                           for r in self.last.results.values())
        return n

    def close(self) -> None:
        self._restore()
        if self.tuner is not None:
            self.tuner.close()
            self.tuner = None

    # ----------------------------------------------------------------- check
    def check(self, control: str | None = None) -> tuple:
        """Compare the kept outputs with the plain reference, and the last
        recording with the space it has to cover. ``control`` names a
        lower precision (``bfloat16``, ``float8_e4m3fn``) in which the
        reference is put in the program's place. Returns ``(correct,
        compared, attempted, failed)``."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from reference import attention as ref
        self.close()
        t0 = time.perf_counter()
        p = self.config["problem"]
        q, k, v = ref.inputs(key_seed(self.seed), p["bh"], p["bh_kv"],
                             p["seq"], p["d"])
        want = ref.attention(q, k, v)
        if control is not None:
            low = getattr(jnp, control)
            got = ref.attention(q, k, v, store=low,
                                dtype=jnp.bfloat16 if control != "bfloat16"
                                else low, precision=None)
            kept = {key: got for key in self.sample}
        else:
            kept = self.kept
        worst = 0.0
        for key in sorted(self.sample):
            out = kept.get(key)
            if out is None:
                err = float("inf")
            else:
                err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - want)))
            worst = max(worst, err)
            print(f"check: {dict(key)} max abs error {err!r}",
                  file=self.log, flush=True)
        scale = float(jnp.max(jnp.abs(want)))
        del q, k, v, want, kept
        self.kept.clear()
        # the recording: every configuration present, labelled from the
        # device it ran on, an ok one with a finite positive time
        label = expected_label(jax.devices()[0].device_kind)
        cache = self.last
        want_ids = {config_id(c) for c in space_configs(self.config)}
        faults = len(want_ids ^ set(cache.results)) + (cache.device != label)
        faults += sum(not (np.isfinite(r.time_s) and r.time_s > 0)
                      for r in cache.results.values() if r.status == "ok")
        print(f"check: {len(self.sample)} outputs and a recording of "
              f"{len(cache.results)} configurations labelled "
              f"{cache.device!r} (reference max |out| {scale!r}) in "
              f"{time.perf_counter() - t0:.1f} s", file=self.log, flush=True)
        limit = float(self.traffic["check"]["max_abs_error"])
        compared = [("attn_max_abs_error", worst, limit),
                    ("recording_faults", faults, 0)]
        correct = worst <= limit and faults == 0
        return correct, compared, self.passes * len(want_ids), self.failed
