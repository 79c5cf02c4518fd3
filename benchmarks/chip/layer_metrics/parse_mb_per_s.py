"""Native parse rate: bytes the parser consumed over the time its
``parser.parse_chunk`` spans took (summed over the parser's threads)."""

from benchmarks.chip.layer_metrics import counter_total, span_seconds

NAME = "parse_mb_per_s"
UNIT = "MB/s"
LAYER = "io + data: split read, native parse"
MOVES = "ingest_rows_per_s"
KINDS = ("ingest",)


def reduce(evidence):
    if evidence["spans"] is None:
        return None
    nbytes = counter_total(evidence["counters"], "dmlc_parser_bytes_total")
    seconds = sum(span_seconds(evidence["spans"], "parser.parse_chunk"))
    if not nbytes or not seconds:
        return None
    return nbytes / 1e6 / seconds
