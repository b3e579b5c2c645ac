"""The mean time to first token (first streamed token's arrival minus the
time the request was due) over the requests due inside the window, from the
client's timestamps. Watched, not bound: it is the part of
``first16_mean_ms`` that the scheduler and prefill decide."""


def read(run):
    return run["values"].get("ttft_mean_ms")
