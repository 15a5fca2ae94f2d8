"""Seconds the engine spent compiling (or loading from the cache) and
draining its serving programs before it went live: the program's
`serve_setup.warmup` span."""

from harness import spans


def read(obs):
    return spans.setup_span_s(obs.get("records"), "serve_setup.warmup")
