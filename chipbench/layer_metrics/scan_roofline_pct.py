"""Layer kernels: the least time the chip could take to read what the
scans of the traced window's statements read, over the time the device
was busy, %. Memory-bound: bytes over the chip's peak HBM bandwidth
(peaks.json); the operations of a scan/aggregate are far under the
compute roof.

Bytes follow the program's `rows_scanned` counter, never the table: the
rows each statement instance's scans counted when it ran alone in
warm-up (pruning by a pushed-down predicate shows there), times the
bytes per row of the columns its scans put on the device. The live mask
and intermediates are left out, so the share is a lower bound."""


def scanned_bytes(run) -> float:
    return float(sum(
        run.rows_scanned[s.instance] * run.row_bytes[s.instance]
        for s in run.trace_completed
    ))


def read(run):
    if run.trace is None or not run.trace["busy_s"] or not run.trace_completed:
        return None
    least_s = scanned_bytes(run) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / run.trace["busy_s"]
