"""The one general traffic generator: a mix is a data file of parameters.

A training mix says what one step is fed: how the inputs are drawn,
what the labels are, and whether every step gets a fresh batch or one
batch stays resident on the device (MXNet's ``--benchmark 1``).  Sizes
come from the configuration (batch, image shape, vocabulary), so one
mix serves any configuration of its kind.  Everything is drawn from
``--seed``; the program is handed only the arrays.
"""
import numpy as np

TRAFFIC_STREAM = 2      # weights use stream 1 of the same seed


class Batch:
    def __init__(self, host, placed):
        self.host = host        # (inputs, labels) as numpy
        self.placed = placed    # whatever the driver's place() returned


class Feed:
    def __init__(self, traffic, config, seed):
        self.traffic, self.config = traffic, config
        self.rng = np.random.default_rng([int(seed), TRAFFIC_STREAM])
        self.place = None       # set by the driver (Driver.build)
        self._resident = None

    def _draw(self):
        t, cfg = self.traffic, self.config
        rows = int(cfg[t["rows_key"]])
        inp, lab = t["inputs"], t["labels"]
        if inp["kind"] == "uniform":
            shape = (rows,) + tuple(cfg[inp["shape_key"]])
            x = self.rng.uniform(inp["low"], inp["high"], shape).astype(
                inp["dtype"])
            extra = None
        elif inp["kind"] == "tokens":
            seq = min(int(inp["seq_len"]), int(cfg[inp["max_len_key"]]))
            extra = self.rng.integers(0, int(cfg[inp["vocab_key"]]),
                                      (rows, seq + 1))
            x = extra[:, :-1].astype(inp["dtype"])
        else:
            raise ValueError("traffic: unknown inputs kind %r" % inp["kind"])
        if lab["kind"] == "class":
            y = self.rng.integers(0, int(cfg[lab["classes_key"]]), (rows,))
        elif lab["kind"] == "next_token":
            y = extra[:, 1:]
        else:
            raise ValueError("traffic: unknown labels kind %r" % lab["kind"])
        return x, np.ascontiguousarray(y).astype(lab["dtype"])

    def next(self):
        if self.traffic["fresh_each_step"]:
            host = self._draw()
            return Batch(host, self.place(host))
        if self._resident is None:
            host = self._draw()
            self._resident = Batch(host, self.place(host))
        return self._resident
