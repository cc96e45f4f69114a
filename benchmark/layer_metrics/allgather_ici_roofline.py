"""allgather_ici_roofline: least wire time of a query's replicated rows over
the seconds its all-gathers took (device_trace). Only on the device kind
whose ICI peak the metric's file states: a CPU has no such roofline.

Both exchange kinds that run an all_gather hold 1/W of their rows on each
chip and send that share to the W-1 peers, so a chip puts (W-1)/W of the
rows' bytes on the wire. The counters count the rows as they ARRIVE: a
BROADCAST's live_bytes counts a row once for every one of the W workers it
reaches (W times its bytes), a GATHER's once (worker 0 alone keeps it,
though the all_gather carries it to every chip). So the rows' own bytes are
broadcast / W + gather, and a chip's least seconds (broadcast / W + gather)
x (W-1) / W over its ICI bytes a second. Over 100% is a fault of the count
and is reported as it reads."""
import jax

from benchmark.harness import collectives


def read(spec, window):
    got = collectives.of_window(window)
    if not got or not got.get("all-gather") or not window["completed"] or \
            jax.devices()[0].device_kind != spec["device_kind"]:
        return None
    before, after = window["before"]["counters"], window["after"]["counters"]

    def gained(name):
        return after.get(name, 0) - before.get(name, 0)

    chips = jax.device_count()
    rows_bytes = (gained(spec["broadcast"]) / chips + gained(spec["gather"])) \
        / window["completed"]
    if chips < 2 or not rows_bytes:
        return None
    least_s = rows_bytes * (chips - 1) / chips / spec["ici_bytes_per_s"]
    return 100.0 * least_s / got["all-gather"]
