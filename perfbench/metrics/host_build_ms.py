"""host_build_ms.gap, .tok (ms, lower is better; layer: cluster and
scheduler). Median duration of the program's ``serving.build`` span over
the dispatches of the traced window: copy-on-write, the numpy metadata,
the sampling arrays, their transfer to the device, the enqueue."""

from harness import spans


def read(run):
    sp = spans.loaded(run)
    return None if sp is None else spans.median_ms(sp["host"],
                                                   "serving.build")
