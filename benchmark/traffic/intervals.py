"""Traffic generator `intervals`: one client, closed loop, no think time,
no ingest; every query brings its OWN range, step and window, at an
interval drawn from the seed inside the bulk, and may name series drawn
from the seed.  This is how a published query set (TSBS) asks: "the max
of every cpu metric on these eight hosts over those eight hours".  A mix
(benchmark/traffic/<name>.json) names this generator and gives it

  templates     a list of {"name", "query", "range_s", "step_s",
                "window_s"}: the query's text, the length of its range,
                its grid step and the lookbehind window its rollup
                states (`[1h]` -> 3600).  In the text `{<draw>:N}` stands
                for N DISTINCT values of a label, drawn from the seed
                without replacement for every query and joined by `|`
                (a regex alternation)
  draws         {"<draw>": "<label>"}: the label each draw takes its
                values from (`{"hosts": "hostname"}`), among the values
                the deployment's series carry
  nocache       send nocache=1 (the reference's -search.disableCache)
  ingest        false: this generator posts nothing, and the
                configuration may say `"ingests": false` (harness.anchor)
  check_sample  how many answers of the window, drawn from the seed, are
                kept for the comparison; the last one is always kept

A query's `start` is a multiple of its step for which the whole of
(start - window, start + range] lies inside the bulk: the server aligns
a start down to the step, so another start would come back on another
grid.  Every template's valid starts are walked in an order drawn from
the seed, again and again, and queries come in balanced rounds (one of
every template a round, in an order drawn from the seed), as `ticker`'s:
every seed asks the same ranges in equal shares, in another order, and
only the drawn series differ.

Warm-up asks every (template, start) once where the template draws
nothing (what the server keeps for a text and a range it has seen, it
has then seen), and WARM_DRAWS queries where it draws: a template's
[series, steps] shape does not hang on WHICH series were drawn, so that
compiles every program the window runs.

The interface is `ticker`'s (its docstring): warm_up(), window(seconds),
by_template(win), close(); `producer_wait_s` is 0.0, there is no
producer.
"""

from __future__ import annotations

import contextlib
import re
import time

import numpy as np

import harness

answered = harness.load_module("traffic", "ticker").answered
WARM_DRAWS = 8
_DRAW = re.compile(r"\{([A-Za-z_]+):([0-9]+)\}")


def valid_starts(data, tmpl: dict) -> list:
    """The multiples of the template's step, in ms, at which
    (start - window, start + range] holds only times the bulk covers:
    from its first scrape less one interval to its newest sample."""
    step, window, span = (int(tmpl[k] * 1000)
                          for k in ("step_s", "window_s", "range_s"))
    first = int(data.ts[:, 0].max()) - data.scrape + window
    last = int(data.ts[:, -1].min()) - span
    return list(range(-(-first // step) * step, last + 1, step))


class Generator:
    def __init__(self, server, data, cfg: dict, mix: dict, seed: int,
                 annotate=None):
        if mix["ingest"]:
            raise ValueError("the generator `intervals` does not ingest")
        self.server, self.mix = server, mix
        self.templates = mix["templates"]
        self.rng = np.random.default_rng([seed, 1])
        self.round = []
        self.starts = [valid_starts(data, t) for t in self.templates]
        for tmpl, starts in zip(self.templates, self.starts):
            if not starts:
                raise ValueError(f"the bulk holds no range of {tmpl['name']}")
        self.walks = [[] for _ in self.templates]
        # a draw's choices: the label's distinct values, in series order
        self.choices = {draw: list(dict.fromkeys(
            l[label] for l in data.labels if label in l))
            for draw, label in mix.get("draws", {}).items()}
        self.annotate = annotate or (lambda name: contextlib.nullcontext())

    def _fill(self, text: str) -> str:
        """The template's text with every {draw:N} drawn."""
        def drawn(m):
            values = self.choices[m.group(1)]
            picked = self.rng.choice(len(values), int(m.group(2)),
                                     replace=False)
            return "|".join(values[i] for i in picked)
        return _DRAW.sub(drawn, text)

    def _next_start(self, i: int) -> int:
        if not self.walks[i]:
            starts = self.starts[i]
            self.walks[i] = [starts[j] for j in
                             self.rng.permutation(len(starts))]
        return self.walks[i].pop()

    def _record(self, i: int, start: int) -> dict:
        """What is asked and what the comparison needs of it: template
        index, text with its draws made, start, end and step (ms)."""
        tmpl = self.templates[i]
        return dict(query=self._fill(tmpl["query"]), template=i, start=start,
                    end=start + int(tmpl["range_s"] * 1000),
                    step=int(tmpl["step_s"] * 1000), n_tails=0)

    def _next_query(self) -> dict:
        if not self.round:
            self.round = list(self.rng.permutation(len(self.templates)))
        i = int(self.round.pop())
        return self._record(i, self._next_start(i))

    def ask(self, rec: dict):
        """-> (seconds, body) of one query_range."""
        with self.annotate("bench:query_range"):
            t0 = time.perf_counter()
            body = self.server.query_range(rec["query"], rec["start"],
                                           rec["end"], rec["step"],
                                           self.mix["nocache"])
            return time.perf_counter() - t0, body

    def close(self) -> None:
        pass

    def warm_up(self) -> int:
        n = 0
        for i, tmpl in enumerate(self.templates):
            starts = [self._next_start(i) for _ in range(WARM_DRAWS)] \
                if _DRAW.search(tmpl["query"]) else self.starts[i]
            for start in starts:
                self.ask(self._record(i, start))
                n += 1
        return n

    def by_template(self, win: dict) -> dict:
        """{template's text: [latencies]}."""
        out = {}
        for asked, lat in zip(win["asked"], win["latencies"]):
            out.setdefault(self.templates[asked["template"]]["query"],
                           []).append(lat)
        return out

    def window(self, seconds: float) -> dict:
        """Queries back to back until `seconds` have passed; the one in
        flight then ends the window."""
        keep = self.mix["check_sample"]
        lat, asked, kept, failed = [], [], [], 0
        last = None
        t_open = time.perf_counter()
        while time.perf_counter() - t_open < seconds:
            rec = self._next_query()
            dt, body = self.ask(rec)
            lat.append(dt)
            failed += not answered(body)
            asked.append(rec)
            last = dict(rec, body=body)
            # reservoir drawn from the seed, as ticker's
            slot = int(self.rng.integers(0, len(lat)))
            if len(kept) < keep:
                kept.append(last)
            elif slot < keep:
                kept[slot] = last
        window_s = time.perf_counter() - t_open
        if last is not None and all(r is not last for r in kept):
            kept.append(last)
        return dict(latencies=lat, asked=asked, failed=failed, kept=kept,
                    window_s=window_s, producer_wait_s=0.0)
