"""The timed path, broken underneath, for test_faults.py: hooks the runner
calls while it builds the program (`harness/runner.Program`)."""
import numpy as np


class _None:
    def pool(self, pool):
        return pool

    def fit_kwargs(self, kw):
        pass


class StateUnchanged(_None):
    """A step that returns its state unchanged: the optimizer moves nothing."""

    def fit_kwargs(self, kw):
        kw["optimizer_params"] = dict(kw["optimizer_params"],
                                      learning_rate=0.0, wd=0.0)


class HalfBatch(_None):
    """Half of the batch left out, the mean taken over the rest: the program
    is fed the first half of the rows twice, so its batch sum over the full
    batch size IS the mean over that half (BatchNorm's statistics too)."""

    def pool(self, pool):
        out = []
        for data, label in pool:
            h = data.shape[0] // 2
            out.append((np.concatenate([data[:h], data[:h]]),
                        np.concatenate([label[:h], label[:h]])))
        return out


def make(name):
    return {None: None, "state_unchanged": StateUnchanged(),
            "half_batch": HalfBatch()}[name]
