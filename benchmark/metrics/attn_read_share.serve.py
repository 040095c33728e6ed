"""The share of the KV cache that the decode steps' attention reads fetch,
in per cent: 100 x ``serving.attn_rows_read`` (cache rows the bounded
reads fetched, each slot's length rounded up to whole blocks, summed on
the device over slots, attention layers and decode steps) over
``serving.attn_rows_pool`` (the pool's rows over the same layers and
steps), both over the whole run. A dense read fetches every row of every
slot, live or not, and counts nothing; a program without the counters
(the parent of the PR that added them) gives None, never 100."""


def read(ctx):
    import mxnet_tpu as mx
    pool = mx.telemetry.counter("serving.attn_rows_pool").value
    if not pool:
        return None
    rows = mx.telemetry.counter("serving.attn_rows_read").value
    print("counters: attention rows read = %d of %d in the pool over the "
          "same layers and decode steps" % (rows, pool), flush=True)
    return 100.0 * rows / float(pool)
