"""Driver ``stream``: an exhaustive sweep streamed in fixed-size chunks.

The feed cycles through the configuration's hardware grid in its
enumeration order and draws every config's DRAM bandwidth uniformly from
the configuration's range, seeded per chunk, so no config repeats within
a window.  The window pulls chunks until ``--seconds`` have passed since
the first pull, then the program drains what is in flight.

Correctness: the reference evaluates every config the window streamed,
chunk by chunk, and keeps their exact front (the bandwidth is a word of
the synthesis digest, so each draw has an area and a clock of its own).
It also evaluates the program's front configs, and the checks are:

* ``rel_err``: the program's front metrics against the reference's;
* ``missed_gap``: the largest relative amount by which the program's
  front fails to match or beat a member of the reference front, on the
  objective where its nearest member falls shortest;
* ``extra_gap``: the largest relative amount by which a member of the
  reference front beats a program front member on both objectives;
* ``unstreamed``: program front members that the window never streamed;
* ``count_err``: configs the program counted against those streamed.
"""

from __future__ import annotations

import itertools
import time

import numpy as np

from harness import reference, spec
from harness.checks import (Check, dominated, extra_gap, missed_gap,
                            pareto_rows, rel_err)

METRICS = ("perf_per_area", "energy_j", "latency_s", "throughput_gmacs")


def hardware_grid(config: dict) -> dict:
    """The configuration's hardware points in enumeration order (PE type,
    array dims, spad scale, GLB size), as index arrays."""
    rows = []
    for t, (r, c), spad, glb in itertools.product(
            config["pe_types"], config["array_dims"],
            config["spad_entries"], config["glb_kbs"]):
        rows.append((reference.PE_TYPES.index(t), r, c, *spad, glb))
    a = np.array(rows, dtype=np.int64)
    return dict(zip(("type", "rows", "cols", "ifmap", "filt", "psum",
                     "glb_kb"), a.T))


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.networks = spec.networks(config)
        (self.network,) = self.networks
        self.grid = hardware_grid(config)
        self.n_hw = len(self.grid["type"])
        self.chunk = int(traffic["chunk_size"])
        self.bw = tuple(config["dram_bw_gbps"])
        self.limits = config["limits"]
        self.seed = seed
        self.workload = self.network.program()

    # -- inputs ------------------------------------------------------------
    def _draw(self, stream: int, i: int):
        """Hardware index and bandwidth of every config of chunk ``i``."""
        rng = np.random.default_rng([stream, self.seed, i])
        h = (i * self.chunk + np.arange(self.chunk)) % self.n_hw
        return h, rng.uniform(self.bw[0], self.bw[1], size=self.chunk)

    def _soa(self, h, bw) -> dict:
        from repro.core.accelerator import soa_from_fields
        g = self.grid
        return soa_from_fields(
            pe_type_idx=g["type"][h], pe_rows=g["rows"][h],
            pe_cols=g["cols"][h], ifmap_spad=g["ifmap"][h],
            filter_spad=g["filt"][h], psum_spad=g["psum"][h],
            glb_kb=g["glb_kb"][h], dram_bw_gbps=bw,
            clock_cap=np.full(len(h), np.inf))

    def _run(self, feed):
        from repro.core.dse import ExploreSpec, run
        return run(ExploreSpec.single(self.workload, feed,
                                      chunk_size=self.chunk, backend="jax"))

    # -- phases ------------------------------------------------------------
    def set_up(self) -> bool:
        """Two chunks of the window's shape, from another stream; returns
        whether the Pallas kernel ran them."""
        res = self._run(self._soa(*self._draw(1, i)) for i in range(2))
        return bool(res.timings["use_pallas"])

    def window(self, seconds: float) -> dict:
        clock: dict = {}
        self.n_chunks = 0

        def feed():
            clock["start"] = time.perf_counter()
            while time.perf_counter() - clock["start"] < seconds:
                yield self._soa(*self._draw(0, self.n_chunks))
                self.n_chunks += 1

        self.result = self._run(feed())
        wall = time.perf_counter() - clock["start"]
        t = self.result.timings
        if t["degraded"] or t["watchdog_redispatches"] \
                or t["cancelled_recomputes"]:
            raise RuntimeError(f"the stream left the device path: {t}")
        return {"metrics": {"sweep_configs_per_s":
                            self.result.n_configs / wall},
                "attempted": self.n_chunks * self.chunk, "failed": 0}

    def kernel_calls(self, spans) -> list:
        """Logical ``(n, l, w, mixed)`` of every kernel call traced."""
        return [(s["attrs"]["n"], self.network.n_layers, 1, False)
                for s in spans if s["name"] == "sweep.synthesize"]

    def release(self) -> None:
        """Keep the front, drop everything else the program holds."""
        res = self.result
        self.front = {k: np.asarray(v) for k, v in res.front_soa.items()}
        self.front_metrics = {m: np.asarray(res.front_metrics[m])
                              for m in METRICS}
        self.n_configs = res.n_configs
        del self.result

    # -- correctness -------------------------------------------------------
    def _hw_index(self) -> np.ndarray:
        g = self.grid
        keys = {k: i for i, k in enumerate(zip(
            g["type"], g["rows"], g["cols"], g["ifmap"], g["filt"],
            g["psum"], g["glb_kb"]))}
        f = self.front
        return np.array([keys.get(k, -1) for k in zip(
            f["pe_type_idx"], f["pe_rows"], f["pe_cols"], f["ifmap_spad"],
            f["filter_spad"], f["psum_spad"], f["glb_kb"])], dtype=np.int64)

    def _hardware(self, h, bw, prec="f64") -> dict:
        g = self.grid
        return reference.hardware(g["type"][h], g["rows"][h], g["cols"][h],
                                  g["ifmap"][h], g["filt"][h], g["psum"][h],
                                  g["glb_kb"][h], bw, prec=prec)

    def _reference(self, h, bw, prec="f64") -> np.ndarray:
        hw = self._hardware(h, bw, prec)
        modes = np.repeat(hw["type"][:, None], self.network.n_layers, axis=1)
        out = reference.evaluate(hw, self.network, modes, prec)
        return np.stack([out[m] for m in METRICS], axis=1)

    def replay(self, front_h, front_bw) -> tuple[np.ndarray, np.ndarray]:
        """Replay the window's feed through the reference: the rows
        ``(-perf/area, energy)`` of the exact front of every config it
        streamed, and which program front members it streamed.

        A config's cycles are at least its layers' compute cycles, so
        its perf/area and energy at those cycles bound what it can
        reach; a config whose bound the running front already matches or
        beats is left out, and the layer model evaluates the rest."""
        pts = np.arange(self.n_hw)
        base = self._hardware(pts, np.ones(self.n_hw))
        modes = np.repeat(base["type"][:, None], self.network.n_layers,
                          axis=1)
        table = self.network.model.table(base, self.network.rows, modes)
        least_cycles = table["compute"].sum(axis=1).astype(np.float64)
        static_pj = table["pj"].sum(axis=1)
        front = np.empty((0, 2))
        streamed = np.zeros(len(front_bw), dtype=bool)
        for i in range(self.n_chunks):
            h, bw = self._draw(0, i)
            for p in np.nonzero(np.isin(bw, front_bw))[0]:
                streamed |= (front_bw == bw[p]) & (front_h == h[p])
            hw = self._hardware(h, bw)
            seconds = least_cycles[h] / (hw["clock_ghz"] * 1e9)
            bound = np.stack([
                -table["macs"] / seconds / 1e9 / hw["area_mm2"],
                (static_pj[h] + hw["leak_mw"] * 1e-3 * seconds * 1e12)
                / 1e12], axis=1)
            bound -= 1e-12 * np.abs(bound)          # room for rounding
            live = np.nonzero(~dominated(front, bound))[0]
            if len(live) == 0:
                continue
            agg = reference.aggregate(
                reference.take(table, h[live]),
                {k: v[live] for k, v in hw.items()})
            rows = np.concatenate([front, np.stack(
                [-agg["perf_per_area"], agg["energy_j"]], axis=1)])
            front = rows[pareto_rows(rows)]
        return front, streamed

    def checks(self, control: bool = False) -> list[Check]:
        front_h = self._hw_index()
        front_bw = self.front["dram_bw_gbps"]
        true_front, streamed = self.replay(front_h, front_bw)
        ok_h = np.where(front_h >= 0, front_h, 0)
        ref_front = self._reference(ok_h, front_bw)
        got = (self._reference(ok_h, front_bw, "bf16") if control else
               np.stack([self.front_metrics[m] for m in METRICS], axis=1))
        err = rel_err(got, ref_front).max() if len(got) else np.inf
        mine = np.stack([-ref_front[:, 0], ref_front[:, 1]],
                        axis=1)[streamed]
        lim = self.limits
        return [
            Check("rel_err", float(err), lim["rel_err"]),
            Check("missed_gap", missed_gap(true_front, mine),
                  lim["missed_gap"]),
            Check("extra_gap", extra_gap(true_front, mine),
                  lim["extra_gap"]),
            Check("unstreamed", float((~streamed).sum()), lim["unstreamed"]),
            Check("count_err", float(abs(self.n_configs
                                         - self.n_chunks * self.chunk)),
                  lim["count_err"]),
        ]
