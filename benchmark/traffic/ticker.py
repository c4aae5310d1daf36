"""Traffic generator `ticker`: one client, closed loop, no think time.
A mix (benchmark/traffic/<name>.json) names its generator and gives it
parameters; this one reads

  queries        the mix's own query list, {"templates": [...], "params":
                 {...}}, or the name of one of the configuration's lists
                 (`queries` in its file: the source's own panel).  In a
                 template {metric} is the configuration's metric; every
                 other {key} names a parameter, whose choices are a list
                 or, given as a string, 0 .. n-1 for the configuration's
                 size of that name ("jobs")
  nocache        send nocache=1 (the reference's -search.disableCache)
  ingest         true: each tick first posts one query step of fresh
                 scrapes of every series and moves the window a step on.
                 The scrapes and their text are made off the window's
                 clock, by one helper process a few ticks ahead
                 (traffic/producer.py): a tick TAKES its scrapes (a pipe
                 read), appends them to the data set the reference reads,
                 and posts the text.  A mix without ingest starts no helper
  preroll_steps  with ingest: the panel has been open this many steps
                 when the window opens.  Warm-up loads their scrapes in
                 one bulk and asks once, which slides the resident window
                 as the ~104th refresh of an open panel does: that
                 program is then compiled before the window, not in it
  check_sample   how many answers of the window, drawn from the seed, are
                 kept for the comparison; the last one is always kept

A tick is [ingest] + one query_range.  The latency is the query_range
call alone; the window is all of the ticks.  The time the client waited
for a tick the helper had not ready is summed (`producer_wait_s`): the
helper makes a tick in a fraction of the time the server takes to
ingest and answer one, so it should be nil, and run.py says so loudly
where it passes 1 % of the window.  Queries come in balanced
rounds: a round asks one query of every template, in an order drawn from
the seed, and each template's queries are walked in an order drawn from
the seed too - every seed asks the same set, and any stretch of the
window holds the templates in equal shares.

The interface run.py asks of a generator: Generator(server, data, cfg,
mix, seed, annotate) with warm_up() -> queries asked, window(seconds) ->
{latencies, asked, failed, kept, window_s, producer_wait_s},
by_template(win) and close(), which stops what the generator started.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

import harness

IMPORT = "/api/v1/import/prometheus"
# full ticks run after the first answers and before the window, where
# the mix ingests: the append of one step's scrapes and the suffix kernel
WARM_TICKS = 3


def expand(cfg: dict, queries) -> list:
    """[(template, [query texts])]: one text for every choice of the
    parameters the template names."""
    spec = cfg["queries"][queries] if isinstance(queries, str) else queries
    out = []
    for tmpl in spec["templates"]:
        texts = [tmpl.replace("{metric}", cfg["metric"])]
        for key, choices in spec.get("params", {}).items():
            if isinstance(choices, str):
                choices = range(int(cfg[choices]))
            if "{" + key + "}" in tmpl:
                texts = [t.replace("{" + key + "}", str(c))
                         for t in texts for c in choices]
        out.append((tmpl, texts))
    return out


class Generator:
    def __init__(self, server, data, cfg: dict, mix: dict, seed: int,
                 annotate=None):
        self.server, self.data, self.mix = server, data, mix
        self.queries = expand(cfg, mix["queries"])
        self.rng = np.random.default_rng([seed, 1])
        self.round = []
        self.walks = [[] for _ in self.queries]
        self.annotate = annotate or (lambda name: contextlib.nullcontext())
        self.producer = None

    def _next_query(self):
        """-> (template index, query text)."""
        if not self.round:
            self.round = list(self.rng.permutation(len(self.queries)))
        i = int(self.round.pop())
        if not self.walks[i]:
            texts = self.queries[i][1]
            self.walks[i] = [texts[j] for j in
                             self.rng.permutation(len(texts))]
        return i, self.walks[i].pop()

    def ask(self, q: str):
        """-> (seconds, body) of one query_range over the current window."""
        d = self.data
        with self.annotate("bench:query_range"):
            t0 = time.perf_counter()
            body = self.server.query_range(q, d.start, d.end, d.step,
                                           self.mix["nocache"])
            return time.perf_counter() - t0, body

    def ingest(self) -> None:
        with self.annotate("bench:build_scrapes"):
            ts, vals, body = self.producer.take()
            self.data.take((ts, vals))
        with self.annotate("bench:import"):
            self.server.post(IMPORT, body)

    def close(self) -> None:
        if self.producer is not None:
            self.producer.close()
            self.producer = None

    def warm_up(self) -> int:
        """Every distinct query of the mix once (a compiled shape can hang
        on the data: a topk gathers as many rows as it chose); where the
        mix ingests, the pre-roll and WARM_TICKS full ticks."""
        n = 0
        if self.mix["ingest"]:
            # the helper imports while the first answers are asked
            self.producer = harness.load_module("traffic",
                                                "producer").Producer()
        for _, texts in self.queries:
            for q in texts:
                self.ask(q)
                n += 1
        if not self.mix["ingest"]:
            return n
        preroll = self.data.advance(self.mix["preroll_steps"]) \
            if self.mix.get("preroll_steps") else None
        # from here on the stream of tails is the helper's: it makes the
        # first ticks while the pre-roll loads
        self.producer.start(self.data.hand_over())
        if preroll is not None:
            harness.load_columnar(self.server, self.data, *preroll)
            self.server.get("/internal/force_flush")
            self.ask(self._next_query()[1])
            n += 1
        for _ in range(WARM_TICKS):
            self.ingest()
            self.ask(self._next_query()[1])
            n += 1
        return n

    def by_template(self, win: dict) -> dict:
        """{template: [latencies]}: the window's latencies by the shape
        asked."""
        out = {}
        for asked, lat in zip(win["asked"], win["latencies"]):
            out.setdefault(self.queries[asked["template"]][0], []).append(lat)
        return out

    def window(self, seconds: float) -> dict:
        """Ticks until `seconds` have passed; the tick in flight then ends
        the window.  Returns latencies (s), counts, and the kept answers."""
        keep = self.mix["check_sample"]
        lat, asked, kept, failed = [], [], [], 0
        last = None
        waited = self.producer.wait_s if self.producer else 0.0
        t_open = time.perf_counter()
        while time.perf_counter() - t_open < seconds:
            if self.mix["ingest"]:
                self.ingest()
            template, q = self._next_query()
            dt, body = self.ask(q)
            lat.append(dt)
            failed += not answered(body)
            asked.append(dict(query=q, template=template,
                              start=self.data.start, end=self.data.end,
                              n_tails=len(self.data.tails)))
            last = dict(asked[-1], body=body)
            # reservoir drawn from the seed: answer i replaces a kept one
            # with probability keep / i
            slot = int(self.rng.integers(0, len(lat)))
            if len(kept) < keep:
                kept.append(last)
            elif slot < keep:
                kept[slot] = last
        window_s = time.perf_counter() - t_open
        if last is not None and all(r is not last for r in kept):
            kept.append(last)
        if self.producer:
            waited = self.producer.wait_s - waited
        return dict(latencies=lat, asked=asked, failed=failed, kept=kept,
                    window_s=window_s, producer_wait_s=waited)


def answered(body: bytes) -> bool:
    """A whole answer: success, and not partial (read from the head of
    the body, so the window pays for no JSON parse)."""
    head = body[:256].replace(b" ", b"")
    return b'"status":"success"' in head and b'"isPartial":false' in head
