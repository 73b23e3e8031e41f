"""What a metric's reader is given: the raw record of one run, and the few
reductions most readers share (which requests were measured, a request's
TTFT and gap, the traced programs by role). A reader that finds nothing to
read returns None, and the metric is left out of the line."""
from __future__ import annotations

import functools

from harness import flops
from harness.cellspec import decode_kernels, load_metric
from harness.peaks import peaks_for
from harness.stats import percentile


class Context:
    def __init__(self, result: dict, chips: int):
        self.r = result
        self.kind = result["kind"]
        self.chips = chips
        self.seconds = result["seconds"]
        self.config, self.traffic = result["config"], result["traffic"]
        self.traced = result.get("traced") or (result.get("worker") or {}).get("traced")
        if self.traced and self.traced.get("error"):
            self.traced = None
        self.percentile = percentile
        self.flops = flops

    def same_as(self, metric: str):
        """For a metric that is another's reading under a name of its own."""
        return load_metric(metric)(self)

    @functools.cached_property
    def peaks(self) -> dict:
        dev = self.r["device"] if self.kind == "serve" else self.r["worker"]
        return peaks_for(dev.get("kind") or dev.get("device_kind"))

    # -- serve ---------------------------------------------------------------
    @staticmethod
    def ok(rec: dict) -> bool:
        return (rec.get("status") == 200 and not rec.get("error") and rec.get("done") is not None
                and rec["n_out"] == rec["out_len"] and not rec.get("bad_tokens"))

    @functools.cached_property
    def window(self) -> tuple:
        return self.r["client"]["w0"], self.r["client"]["w1"]

    @functools.cached_property
    def measured(self) -> list:
        """Open loop: the requests due inside the window. Closed loop: every
        request the clients sent (all must complete)."""
        recs = self.r["client"]["records"]
        if self.r["plan"]["loop"] == "open":
            return [x for x in recs if x["phase"] == "window"]
        return recs

    @functools.cached_property
    def finished(self) -> list:
        return [x for x in self.measured if self.ok(x)]

    @functools.cached_property
    def timed(self) -> list:
        """Requests whose latency counts: open loop, every measured one that
        finished; closed loop, those whose first token fell inside the window."""
        if self.r["plan"]["loop"] == "open":
            return self.finished
        w0, w1 = self.window
        return [x for x in self.finished if w0 <= x["t_first"] < w1]

    @staticmethod
    def ttft_ms(rec) -> float:
        return (rec["t_first"] - rec["due"]) * 1e3

    @staticmethod
    def tpot_ms(rec) -> float:
        return (rec["t_last"] - rec["t_first"]) / (rec["n_out"] - 1) * 1e3

    def ttfts(self) -> list:
        return [self.ttft_ms(x) for x in self.timed]

    def tpots(self) -> list:
        return [self.tpot_ms(x) for x in self.timed if x["n_out"] > 1]

    def tokens_in_window(self) -> int:
        w0, w1 = self.window
        return sum(n for x in self.r["client"]["records"] for t, n in x["chunks"] if w0 <= t < w1)

    # -- the traced programs, by role ---------------------------------------
    def module(self, fragment: str):
        """(name, seconds, runs) of the traced program whose name holds it."""
        if not self.traced:
            return None
        for name, secs in self.traced["module_s"].items():
            if fragment in name:
                return name, secs, self.traced["module_runs"][name]
        return None

    def kernel_of(self, fragment: str, name: str | None = None):
        """{'seconds', 'calls'} of the Mosaic kernels inside that program: all
        of them, or with `name` those whose instruction's name holds it (None
        where the program has no such kernel)."""
        m = self.module(fragment)
        if not m:
            return None
        if name is None:
            return self.traced["kernel"].get(m[0])
        named = [k for n, k in (self.traced.get("kernels") or {}).get(m[0], {}).items() if name in n]
        return {key: sum(k[key] for k in named) for key in ("seconds", "calls")} if named else None

    def traced_decode_steps(self):
        """Decode steps inside the traced window, which the trace itself says:
        every step calls its kernels a known number of times. An architecture
        file that defines `decode_kernels` says which kernel how often (the
        first one it names is counted); without it the decode program has one
        kernel, the paged one, called once a layer."""
        declared = decode_kernels(self.config)
        if declared:
            name, calls_a_step = next(iter(declared.items()))
            k = self.kernel_of("_decode_impl", name)
        else:
            k, calls_a_step = self.kernel_of("_decode_impl"), self.config["num_hidden_layers"]
        return k["calls"] / calls_a_step if k else None
