"""Share of the window the consumer spent waiting for a host-to-device
copy that had not hidden behind the next batch's host work: summed
``loader.transfer.wait`` spans over the window.  (``loader.transfer`` and
``dmlc_transfer_seconds_total{phase=dispatch}`` time the asynchronous
dispatch of ``device_put``, not the copy, and are not read.)"""

from benchmarks.chip.layer_metrics import span_seconds

NAME = "transfer_exposed_share"
UNIT = "%"
LAYER = "bridge: loader.py to the device"
MOVES = "ingest_rows_per_s"
KINDS = ("ingest",)


def reduce(evidence):
    if evidence["spans"] is None:
        return None
    waits = span_seconds(evidence["spans"], "loader.transfer.wait")
    window_s = evidence["window"].get("window_s")
    if not waits or not window_s:
        return None
    return 100.0 * sum(waits) / window_s
