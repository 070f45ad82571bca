"""Training cells whose clients hold a number of images that the batch
size does not divide: ``train.py``'s cell, with the edge check fed
each client's whole label counts.

A client's epoch drops its last partial batch (``ClientData.epoch``),
so its steps see fewer labels than the client reports to its edge: the
edge accumulates the client's whole label distribution and size (Eq.
19), and the SH weights (Eq. 24) come from them too.  ``train.py``
sums the labels of a client's batches, which equals those counts only
where the batch size divides every client's data.  Here the counts are
read from the program's own clients, in the order they ran, which a
pass-through wrapper on each client's ``epoch`` records during round 1.
Everything else is ``train.py``'s.
"""
from __future__ import annotations

import os

import numpy as np

from lib import harness

_train = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "train.py"))


class Cell(_train.Cell):
    def _record_round(self, r):
        self._ran = []
        datas = [cl.data for cl in self.trainer.clients]
        for cl in self.trainer.clients:
            def epoch(orig=cl.data.epoch, cid=cl.cid):
                self._ran.append(cid)
                return orig()
            cl.data.epoch = epoch
        try:
            super()._record_round(r)
        finally:
            for data in datas:
                del data.epoch                  # back to the class's

    def _edge_check(self, finals, labels, start, diff_norms):
        clients = self.trainer.clients
        nc = clients[0].num_classes
        ran = self._ran[::self.spec.fl.local_epochs]
        whole = [np.bincount(np.asarray(clients[c].data.labels),
                             minlength=nc) for c in ran]
        if len(whole) != len(labels):
            return float("inf")
        return super()._edge_check(finals, whole, start, diff_norms)
