"""The one traffic generator: a mix file's parameters and --seed -> requests.

A mix (`benchmark/traffic/<name>.json`) gives
  queries   [{"query": <name under queries/>, "weight": n,
              "parameters": {<field>: <spec>}}]
  loop      "closed" (a client sends its next query when the last returned) or
            "open" (queries are due at `rate_per_s`, walls count from due time)
  clients   threads, each with a connection of its own
A parameter spec is {"type": "int", "lo", "hi"}, {"type": "decimal", "lo",
"hi", "step"} (text, exact) or {"type": "choice", "values"}: the ranges the
TPC-H specification gives qgen. One run draws ONE parameter set per query
from the seed (a TPC-H stream's substitution set) and uses it for the warm-up
and every query of the window. The order of a mixed queue is the weights'
block, shuffled by the seed and repeated: every seed sends the same work.
"""
import random
from decimal import Decimal


def draw(spec, rng):
    kind = spec["type"]
    if kind == "int":
        return rng.randint(spec["lo"], spec["hi"])
    if kind == "decimal":
        lo, hi, step = (Decimal(spec[k]) for k in ("lo", "hi", "step"))
        return str(lo + step * rng.randint(0, int((hi - lo) / step)))
    if kind == "choice":
        return rng.choice(spec["values"])
    raise ValueError(f"unknown parameter type {kind!r}")


class Plan:
    """What one run sends: per query its parameters and SQL, and the order."""

    def __init__(self, traffic, queries, seed):
        rng = random.Random(int(seed))
        self.loop = traffic["loop"]
        self.clients = int(traffic["clients"])
        self.rate_per_s = traffic.get("rate_per_s")
        if self.loop not in ("closed", "open"):
            raise ValueError(f"loop {self.loop!r} is neither closed nor open")
        if self.loop == "open" and not self.rate_per_s:
            raise ValueError("an open loop needs rate_per_s")
        self.params, self.sql, block = {}, {}, []
        for q in traffic["queries"]:
            name = q["query"]
            self.params[name] = {field: draw(spec, rng) for field, spec
                                 in sorted(q.get("parameters", {}).items())}
            self.sql[name] = queries[name].template.format(**self.params[name])
            block += [name] * int(q.get("weight", 1))
        rng.shuffle(block)
        self.block = block

    def query_at(self, i):
        return self.block[i % len(self.block)]
