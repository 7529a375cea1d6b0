"""Share (%) of the traced serving window in which no operation ran on the device. Layer:
device. Moves ``itl_p95_ms`` (between two decode programs the chip waits for the host).
"""


def read(result, ctx):
    if result.trace is None or "traced_serve_s" not in result.facts:
        return None
    return 100.0 * result.trace.idle_share
