"""Megabytes (10^6 B) that the regex accelerator's compiled rules hold on
the device, from the program's process-wide gauge
``meili.regex.device_bytes`` (``repro.obs.spans.gauges``), set when the
rules are compiled (program counter). A program that keeps no such gauge
reads nothing."""


def read(run):
    try:
        from repro.obs import spans
        value = spans.gauges().get("meili.regex.device_bytes")
    except (ImportError, AttributeError):
        return None
    return value / 1e6 if value else None
