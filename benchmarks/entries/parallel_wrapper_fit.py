"""``ParallelWrapper(net, shard_update=True).fit(iterator)`` on a ``data``
mesh of the cell's chips, over an ``AsyncDataSetIterator`` of host batches:
GSPMD partitions the step, the updater state is sharded (ZeRO-1), the host
feeds every chip."""

from __future__ import annotations

from . import graph_fit


class Entry(graph_fit.Entry):
    def __init__(self, program, net, data, traffic, devices):
        from deeplearning4j_tpu.data.dataset import (AsyncDataSetIterator,
                                                     NumpyDataSetIterator)
        from deeplearning4j_tpu.parallel.data_parallel import (
            ParallelWrapper, make_mesh)
        super().__init__(program, net, data, traffic, devices)
        x, y = data
        self.it = AsyncDataSetIterator(
            NumpyDataSetIterator(x, y, batch_size=self.batch))
        self.pw = ParallelWrapper(net, mesh=make_mesh(devices),
                                  shard_update=True)

    def _fit(self):
        self.pw.fit(self.it)

    def release(self):
        super().release()
        self.pw = None
