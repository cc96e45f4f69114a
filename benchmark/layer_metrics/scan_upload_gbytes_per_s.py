"""scan_upload_gbytes_per_s: 1e9 bytes a second through the scan pipeline's
upload stage (program_counter: what `scan.pipeline.bytes` gained in the window
over what `scan.pipeline.upload_busy_s` gained). None where no page was
uploaded, or on a tree without the counters."""


def read(spec, window):
    before, after = window["before"]["counters"], window["after"]["counters"]
    nbytes = after.get(spec["bytes"], 0) - before.get(spec["bytes"], 0)
    seconds = after.get(spec["seconds"], 0) - before.get(spec["seconds"], 0)
    if nbytes <= 0 or seconds <= 0:
        return None
    return nbytes / seconds / 1e9
