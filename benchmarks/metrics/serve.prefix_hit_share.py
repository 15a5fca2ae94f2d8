"""Share (%) of the prompt tokens of the requests due in the window that
the prefix cache served from cached pages instead of prefilling them
(`cached_tokens` / `prompt_len` of the program's `serve_request` records).
The window's requests are the run's last ones: the feed numbers requests
`q<n>` in the order they fall due and sends none after the window, and
the driver keeps one first-token time for each request due in it. Under
95% the cell is not measuring what it says (its contexts are being
prefilled, not served). Nothing to read where the records lack the
counter."""


def read(obs):
    due = len(obs.get("ttft_s") or ())
    records = sorted(
        (r for r in obs.get("records") or ()
         if r.get("record") == "serve_request" and "cached_tokens" in r
         and str(r.get("id", "")).startswith("q")),
        key=lambda r: int(r["id"][1:]))[-due:] if due else []
    prompt = sum(r["prompt_len"] for r in records)
    if not prompt:
        return None
    return 100.0 * sum(r["cached_tokens"] for r in records) / prompt
