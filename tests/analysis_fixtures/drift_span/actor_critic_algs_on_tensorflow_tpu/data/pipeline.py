# Drift checker fixture emitter: TimeSplit counters fed by ``span``.
from utils import metric_names, profiling
from utils.metrics import TimeSplit


class Pipeline:
    def __init__(self):
        self.split = TimeSplit()
        self.device_split = TimeSplit(prefix=metric_names.DEVICE)

    def get(self, seconds):
        with self.split.span("stall_s"):
            pass
        self.split.add("transfer_s", seconds)
        with self.device_split.span("collect_s"):
            pass
        with self.split.span("stal_s"):  # EXPECT: DRIFT002
            pass
        # A bare trace span has no counter and is no metric key.
        with profiling.span("pipeline_sentinel_check"):
            pass
