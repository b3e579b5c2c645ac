"""The 90th percentile of time to first token (first streamed token's
arrival minus the time the request was due) over the requests due inside the
window, from the client's timestamps. Watched, not bound: see PERF.md
section 2 for the spreads that demoted it."""


def read(run):
    return run["values"].get("ttft_p90_ms")
