"""``input_source: device``: the training set lives on the device, placed once
in set-up, and every step of the scanned K-step program draws each worker's
fresh rows from it in-graph (``engine.build_sampled_multi_step``).  What a
dispatch is handed is that resident data set, so the host does no work per
dispatch."""


class Feed:
    def __init__(self, cell):
        self.cell = cell
        self.resident = None
        self.multi = cell.engine.build_sampled_multi_step(
            cell.experiment.loss, cell.tx, repeat_steps=cell.unroll,
            batch_size=cell.experiment.batch_size)

    def start(self):
        """Set-up's part: the data set onto the device(s); returns what to
        wait on."""
        arrays = self.cell.arrays
        if arrays is None:
            raise SystemExit("experiment %r exposes no train_arrays(): it cannot be sampled "
                             "on the device" % self.cell.spec["config_data"]["experiment"])
        self.resident = self.cell.engine.replicate(arrays)
        return self.resident

    def next(self):
        """The data of one dispatch."""
        return self.resident

    def close(self):
        self.resident = None
