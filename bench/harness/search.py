"""Drivers ``nsga2`` and ``serving``: search campaigns run back to back.

Each campaign is one ``repro.core.dse.run`` of an nsga2 search over the
configuration's hardware factors and per-layer precisions, seeded from
``--seed`` and the campaign's index; a ``serving`` campaign also replays a
request trace drawn from that seed on the fleet simulator.  The window
starts campaigns until ``--seconds`` have passed; the mix's ``metric``
(seconds per campaign) is the time from the first start to the last end
over the campaigns run.

Correctness, over every campaign of the window: the reference scores the
returned front and final population genome by genome, and the checks
are:

* ``rel_err``: the program's hardware and serving objectives against the
  reference's (for serving, against the reference fleet at the step
  within the limit that matches best: see ``reference.fleet_outcomes``);
* ``noise_rel_err``: the accuracy-noise objective against the
  reference's;
* ``uncovered``: population genomes that no front genome matches or
  beats within the limits (the front is the archive of every genome the
  search evaluated);
* ``extra``: front genomes that another returned genome beats beyond the
  limits.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from harness import reference, spec
from harness.checks import Check, covered, outranked, rel_err

N_HW_GENES = 5
NOISE = ("accuracy_noise", "worst_accuracy_noise")


def campaign_seed(seed: int, index: int, stream: int = 0) -> int:
    """The seed of campaign ``index`` of a run; set-up draws from
    ``stream`` 1, the window from 0."""
    a, b = np.random.SeedSequence([stream, seed, index]).generate_state(2)
    return (int(a) << 31) ^ int(b)


def draw_arrivals(spec: dict, seed: int):
    """``(arrival_s, prompt_tokens, decode_tokens)`` of one trace:
    Poisson arrivals; phase lengths uniform over inclusive ranges."""
    if spec["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {spec['kind']!r}")
    rng = np.random.default_rng(seed)
    n = int(spec["n_requests"])
    arrival = np.cumsum(rng.exponential(1.0 / spec["rate_rps"], size=n))
    lo, hi = spec["prompt_tokens"]
    prompt = rng.integers(lo, hi + 1, size=n, dtype=np.int64)
    lo, hi = spec["decode_tokens"]
    decode = rng.integers(lo, hi + 1, size=n, dtype=np.int64)
    return arrival, prompt, decode


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.networks = spec.networks(config)
        self.config = config
        self.mix = traffic
        self.serving = traffic["driver"] == "serving"
        self.objectives = tuple(traffic["objectives"])
        self.limits = config["limits"]
        self.seed = seed
        self.workloads = tuple(n.program() for n in self.networks)
        self.overrides = dict(
            pe_types=tuple(config["pe_types"]),
            array_dims=tuple(tuple(d) for d in config["array_dims"]),
            spad_scales=tuple(config["spad_scales"]),
            glb_kbs=tuple(config["glb_kbs"]),
            bws=tuple(config["dram_bw_levels"]))

    # -- inputs ------------------------------------------------------------
    def _trace(self, seed: int):
        from repro.serving.traffic import TrafficTrace
        arrival, prompt, decode = draw_arrivals(self.mix["arrivals"], seed)
        return TrafficTrace(name=f"{self.mix['arrivals']['kind']}-{seed}",
                            arrival_s=arrival, prompt_tokens=prompt,
                            decode_tokens=decode,
                            slo_s=self.mix["arrivals"]["slo_s"])

    def _spec(self, seed: int, budget: int):
        from repro.core.dse import ExploreSpec
        m = self.mix
        kw = dict(method=m["method"], budget=budget,
                  objectives=self.objectives, seed=seed,
                  chunk_size=m["chunk_size"], backend="jax",
                  space_overrides=self.overrides, pop_size=m["pop_size"],
                  mutation_rate=m["mutation_rate"])
        if len(self.workloads) > 1:
            return ExploreSpec.many(self.workloads, precision="mixed", **kw)
        if self.serving:
            kw.update(traffic=self._trace(seed), n_slots=m["n_slots"])
        return ExploreSpec.mixed(self.workloads[0], **kw)

    # -- phases ------------------------------------------------------------
    def set_up(self) -> bool:
        """One short campaign, then one evaluation at every padded batch
        size a campaign of this population can dispatch; returns whether
        the Pallas kernel ran them."""
        from repro.core.dse import run
        from repro.explore.search import Evaluator
        from repro.explore.space import space_for_workload, \
            space_for_workloads
        pop = int(self.mix["pop_size"])
        warm = campaign_seed(self.seed, 0, stream=1)
        res = run(self._spec(warm, 2 * pop))
        multi = len(self.workloads) > 1
        space = (space_for_workloads(self.workloads, **self.overrides)
                 if multi else
                 space_for_workload(self.workloads[0], **self.overrides))
        ev = Evaluator(space, self.workloads if multi else self.workloads[0],
                       self.objectives, backend="jax",
                       chunk_size=self.mix["chunk_size"],
                       traffic=self._trace(warm) if self.serving else None,
                       n_slots=self.mix.get("n_slots", 8))
        rng = np.random.default_rng(warm)
        size = 8
        while size < 2 * pop:
            ev.evaluate(space.random_population(size, rng))
            size *= 2
        return bool(res.stats["use_pallas"] and ev.use_pallas)

    def window(self, seconds: float) -> dict:
        from repro.core.dse import run
        self.campaigns = []
        start = time.perf_counter()
        ends = [start]
        while ends[-1] - start < seconds:
            seed = campaign_seed(self.seed, len(self.campaigns))
            res = run(self._spec(seed, int(self.mix["budget"])))
            ends.append(time.perf_counter())
            self.campaigns.append(dict(
                seed=seed, genomes=np.asarray(res.genomes),
                F=np.asarray(res.front_objectives),
                pop=np.asarray(res.population),
                popF=np.asarray(res.population_objectives)))
        n = len(self.campaigns)
        print("campaign seconds: " + " ".join(
            f"{b - a:.3f}" for a, b in zip(ends, ends[1:])), file=sys.stderr)
        return {"metrics": {self.mix["metric"]: (ends[-1] - start) / n},
                "attempted": n, "failed": 0}

    def kernel_calls(self, spans) -> list:
        """Logical ``(n, l, w, mixed)`` of every kernel call traced: one
        per evaluation that found genomes outside the memo."""
        l = sum(n.n_layers for n in self.networks)
        return [(s["attrs"]["kernel"], l, len(self.networks), True)
                for s in spans if s["name"] == "explore.evaluate"
                and s["attrs"].get("kernel")]

    def release(self) -> None:
        """Campaign results are plain arrays already."""

    # -- correctness -------------------------------------------------------
    def _decode(self, genomes: np.ndarray, prec: str):
        c = self.config
        g = np.asarray(genomes, dtype=np.int64)
        types = np.array([reference.PE_TYPES.index(t)
                          for t in c["pe_types"]])[g[:, 0]]
        dims = np.array(c["array_dims"])[g[:, 1]]
        spad = np.array(c["spad_entries"])[g[:, 2]]
        hw = reference.hardware(
            types, dims[:, 0], dims[:, 1], spad[:, 0], spad[:, 1],
            spad[:, 2], np.array(c["glb_kbs"])[g[:, 3]],
            np.array(c["dram_bw_levels"], dtype=np.float64)[g[:, 4]],
            prec=prec)
        return hw, g[:, N_HW_GENES:]

    def _reference(self, genomes, got, trace_seed, prec):
        """Reference objective rows of ``genomes``; a serving row takes
        the fleet outcome nearest to the program's row ``got``."""
        hw, modes = self._decode(genomes, prec)
        table = reference.noise_table(prec)
        per_net, start = [], 0
        for net in self.networks:
            m = modes[:, start:start + net.n_layers]
            start += net.n_layers
            agg = reference.evaluate(hw, net, m, prec)
            agg["noise"] = reference.accuracy_noise(m, net, table)
            per_net.append(agg)
        noise = np.max([a["noise"] for a in per_net], axis=0)
        cols = {"neg_worst_perf_per_area":
                -np.min([a["perf_per_area"] for a in per_net], axis=0),
                "total_energy_j": np.sum([a["energy_j"] for a in per_net],
                                         axis=0),
                "worst_accuracy_noise": noise, "accuracy_noise": noise}
        fleet_cols = [k for k, o in enumerate(self.objectives)
                      if o in ("p99_latency_s", "energy_per_token_j")]
        out = np.zeros((len(genomes), len(self.objectives)))
        for k, name in enumerate(self.objectives):
            if k not in fleet_cols:
                out[:, k] = cols[name]
        if fleet_cols:
            rtol = float(self.config["step_rtol"]) if prec == "f64" else 0.0
            self._fleet_columns(out, per_net[0], got, fleet_cols, trace_seed,
                                rtol)
        return out

    def _fleet_columns(self, out, agg, got, cols, trace_seed, rtol):
        spec = self.mix["arrivals"]
        arrival, prompt, decode = draw_arrivals(spec, trace_seed)
        svc = prompt + decode - 1
        pick = [("p99_latency_s", "energy_per_token_j").index(
            self.objectives[k]) for k in cols]
        for i in range(len(out)):
            outcomes = np.array(reference.fleet_outcomes(
                agg["latency_s"][i], agg["energy_j"][i], arrival, svc,
                int(self.mix["n_slots"]), rtol))
            outcomes = outcomes[:, pick]
            err = rel_err(outcomes, got[i, cols][None, :]).max(axis=1)
            out[i, cols] = outcomes[int(np.argmin(err))]

    def checks(self, control: bool = False) -> list[Check]:
        noise = np.array([o in NOISE for o in self.objectives])
        tol = np.where(noise, self.limits["noise_rel_err"],
                       self.limits["rel_err"])
        worst = np.zeros(2)
        uncovered = extra = 0
        for c in self.campaigns:
            genomes = np.concatenate([c["genomes"], c["pop"]])
            got = np.concatenate([c["F"], c["popF"]])
            if control:
                got = self._reference(genomes, got, c["seed"], "bf16")
            ref = self._reference(genomes, got, c["seed"], "f64")
            err = rel_err(got, ref)
            worst = np.maximum(worst, [err[:, ~noise].max(initial=0.0),
                                       err[:, noise].max(initial=0.0)])
            nf = len(c["genomes"])
            uncovered += sum(not covered(r, ref[:nf], tol)
                             for r in ref[nf:])
            extra += sum(outranked(ref[i], np.delete(ref, i, axis=0), tol)
                         for i in range(nf))
        lim = self.limits
        return [Check("rel_err", float(worst[0]), lim["rel_err"]),
                Check("noise_rel_err", float(worst[1]),
                      lim["noise_rel_err"]),
                Check("uncovered", float(uncovered), lim["uncovered"]),
                Check("extra", float(extra), lim["extra"])]
