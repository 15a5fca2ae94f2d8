"""Hand-built paged K/V pools for the tests, in the engine's shape.

The tests fill pools token by token as ``[num_pages, page_size, heads,
head_dim]``, which is the natural way to write them down; the engine's
resident pools are lane-dense, ``[num_pages, page_size, heads * head_dim]``
(ops/paged_attention.py). One helper takes the first to the second.
"""


def fold_heads(pool):
    """``[num_pages, page_size, heads, head_dim]`` -> the lane-dense
    ``[num_pages, page_size, heads * head_dim]`` (head ``n`` owns lanes
    ``n * head_dim .. (n + 1) * head_dim - 1``). numpy or jax arrays."""
    return pool.reshape(*pool.shape[:2], -1)
