"""The live-recording generator for the Mamba-2 SSD scan: ``live_record``'s
kernel author recording a kernel on the chip with ``Tuner.record``,
where the kernel's entry point is ``ssd_scan``, a program is a pair
(``chunk``, ``head_block``), and the check is against
``reference/ssd.py``.

Configuration (``configs/<name>.json``, ``"kind": "live_record_ssd"``):
as ``live_record``'s, with ``problem`` ``{bh, bh_g, seq, p, n}``; besides
the sizes ``space.divides`` names, ``head_block`` has to divide
``bh / bh_g``, the heads of one group of B/C.

Traffic (``traffic/<name>.json``):

  mode       ``sweep`` or ``cold``
  check      ``{"sample": k, "max_abs_error": e}`` as in ``live_record``
  strategy, max_evals
             (``cold``) what each pass records

``sweep``: as ``live_record``. Each pass is a fresh exhaustive recording
of the space from no output and cleared jit caches; the programs come
from the persistent cache that set-up filled. The outputs of k programs
drawn from the seed, and of the one with the smallest blocks, are kept.

``cold``: the persistent compile cache is off for the whole process, so
every program a pass meets compiles, as on a new shape or after a JAX
upgrade. Each pass clears the jit caches and records ``max_evals``
configurations drawn by ``strategy`` at the seed ``1 + pass``, the same in
every run, so that runs meet the same programs; set-up records one pass at
seed 0. ``--seed`` draws only the checked outputs: those of k of the last
pass's evaluations, and of its smallest program. The recording check
holds the pass's budget: exactly ``max_evals`` configurations of the
space.
"""
from __future__ import annotations

import glob
import os
import random
import sys
import time

import harness

base = harness.load_module(os.path.join(harness.HERE, "kinds",
                                        "live_record.py"),
                           "chipbench_kind_live_record_base")

ENTRY = "ssd_scan"
PROGRAM = ("chunk", "head_block")
expected_label = base.expected_label
key_seed = base.key_seed
config_id = base.config_id


def space_configs(config: dict) -> list:
    """``live_record``'s configurations, less those whose ``head_block``
    does not divide the heads of a group."""
    p = config["problem"]
    return [c for c in base.space_configs(config)
            if (p["bh"] // p["bh_g"]) % c["head_block"] == 0]


def program(conf) -> tuple:
    """What selects the program: the chunk length and the head block."""
    return tuple((k, conf[k]) for k in PROGRAM)


def smallest(programs):
    return min(programs, key=lambda prog: [v for _, v in prog])


class Generator(base.Generator):

    def __init__(self, cell, seed: int, log=sys.stdout):
        super().__init__(cell, seed, log=log)
        self.cold = self.traffic["mode"] == "cold"
        self.met: list = []        # cold: programs of this pass, in order
        self.positions: set = set()
        if self.cold:
            import jax
            from jax.experimental.compilation_cache import compilation_cache
            jax.config.update("jax_enable_compilation_cache", False)
            compilation_cache.reset_cache()

    # ------------------------------------------------------------ interception
    def _keep_outputs(self) -> None:
        """Keep the outputs of the checked programs as the timed path
        makes them: the live objective calls ``ssd_scan`` by name."""
        import importlib
        self._module = importlib.import_module("repro.kernels.ssd")
        entry = self._entry = getattr(self._module, ENTRY)
        gen = self

        def kernel(*args, **kw):
            out = entry(*args, **kw)
            gen._keep(program(kw), out)
            return out
        setattr(self._module, ENTRY, kernel)

    def _keep(self, key, out) -> None:
        if not self.cold:
            if key in self.sample:
                self.kept[key] = out
            return
        if key not in self.met:
            self.met.append(key)
        least = smallest(self.met)
        chosen = {k for i, k in enumerate(self.met) if i in self.positions}
        chosen.add(least)
        for k in set(self.kept) - chosen:
            del self.kept[k]
        if key in chosen:
            self.kept[key] = out

    def _restore(self) -> None:
        if self._module is not None:
            setattr(self._module, ENTRY, self._entry)
            self._module = None

    # ---------------------------------------------------------------- set-up
    def _program_matches(self) -> None:
        """Refuse, before any recording, a program whose ``ssd`` space has
        other tunables than the cell records."""
        from repro.kernels import get_kernel
        have = [t.name for t in get_kernel(self.kernel).space(
            self.problem).tunables]
        want = list(self.config["space"]["tunables"])
        if have != want:
            raise SystemExit(f"chipbench: the program's {self.kernel} space "
                             f"has the tunables {have}; the cell records "
                             f"{want}")

    def setup(self) -> None:
        from repro.api import Tuner
        self._program_matches()
        rng = random.Random(self.seed)
        k = int(self.traffic["check"]["sample"])
        if self.cold:
            n = int(self.traffic["max_evals"])
            self.positions = set(rng.sample(range(n), min(k, n)))
        else:
            programs = sorted({program(c)
                               for c in space_configs(self.config)})
            self.sample = set(rng.sample(programs, min(k, len(programs))))
            self.sample.add(smallest(programs))
        self._keep_outputs()
        self.tuner = Tuner(seed=0)
        self._pass()  # compiles every program it meets, or loads it

    def _pass(self) -> int:
        if not self.cold:
            return super()._pass()
        for path in glob.glob(self.out[:-len(".json.gz")] + "*"):
            os.remove(path)
        import jax
        jax.clear_caches()
        self.met = []
        self.kept.clear()
        # set-up's pass at seed 0, the window's pass p at seed 1 + p
        self.tuner.seed = 0 if self.last is None else 1 + self.passes
        run = self.tuner.record(
            self.kernel, runner="live", problem=self.problem,
            repeats=int(self.config["repeats"]),
            strategy=self.traffic["strategy"],
            max_evals=int(self.traffic["max_evals"]), out=self.out)
        self.last = run.cache
        return len(run.cache.results)

    # ----------------------------------------------------------------- check
    def check(self, control: str | None = None) -> tuple:
        """Compare the kept outputs with the plain reference, and the last
        recording with what it has to hold. ``control`` names a lower
        precision (``bfloat16``, ``float8_e4m3fn``) in which the reference
        is put in the program's place. Returns ``(correct, compared,
        attempted, failed)``."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from reference import ssd as ref
        self.close()
        t0 = time.perf_counter()
        p = self.config["problem"]
        args = ref.inputs(key_seed(self.seed), p["bh"], p["bh_g"], p["seq"],
                          p["p"], p["n"])
        want = ref.scan(*args)
        checked = sorted(self.kept) if self.cold else sorted(self.sample)
        if control is not None:
            low = getattr(jnp, control)
            got = ref.scan(*args, store=low,
                           dtype=jnp.bfloat16 if control != "bfloat16"
                           else low, precision=None)
            kept = {key: got for key in checked}
        else:
            kept = self.kept
        worst = 0.0 if checked else float("inf")
        for key in checked:
            out = kept.get(key)
            err = float("inf") if out is None else \
                float(jnp.max(jnp.abs(out.astype(jnp.float32) - want)))
            worst = max(worst, err)
            print(f"check: {dict(key)} max abs error {err!r}",
                  file=self.log, flush=True)
        scale = float(jnp.max(jnp.abs(want)))
        del args, want, kept
        self.kept.clear()
        # the recording: the configurations it has to hold, labelled from
        # the device it ran on, an ok one with a finite positive time
        label = expected_label(jax.devices()[0].device_kind)
        cache = self.last
        space_ids = {config_id(c) for c in space_configs(self.config)}
        got_ids = set(cache.results)
        if self.cold:
            per_pass = int(self.traffic["max_evals"])
            faults = (len(got_ids) != per_pass) + len(got_ids - space_ids)
        else:
            per_pass = len(space_ids)
            faults = len(space_ids ^ got_ids)
        faults += cache.device != label
        faults += sum(not (np.isfinite(r.time_s) and r.time_s > 0)
                      for r in cache.results.values() if r.status == "ok")
        print(f"check: {len(checked)} outputs and a recording of "
              f"{len(got_ids)} configurations labelled {cache.device!r} "
              f"(reference max |out| {scale!r}) in "
              f"{time.perf_counter() - t0:.1f} s", file=self.log, flush=True)
        limit = float(self.traffic["check"]["max_abs_error"])
        compared = [("ssd_max_abs_error", worst, limit),
                    ("recording_faults", faults, 0)]
        correct = worst <= limit and faults == 0
        return correct, compared, self.passes * per_pass, self.failed
