# Drift checker fixture registry: TimeSplit keys reached through
# ``.span("name")`` as through ``.add("name", seconds)``.
PIPELINE = "pipeline_"
DEVICE = "device_"

METRIC_NAMES: dict = {
    PIPELINE + "stall_s": "pipeline.py, by span (quiet)",
    PIPELINE + "transfer_s": "pipeline.py, by add (quiet)",
    DEVICE + "collect_s": "pipeline.py, by span on a bound prefix (quiet)",
    PIPELINE + "ghost_s": "no span or add emits it",  # EXPECT: DRIFT003
}
