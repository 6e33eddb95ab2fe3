"""Share of the window's generate_batch calls (`volume`/`ec.generate_batch`
spans) that the engine spent in its `ec`/`write` spans; the warm-up
call's spans are left out (span_share.window). The engine runs
write_ec_files_batched in one thread, so the five stage shares add up to
nearly the whole call."""

from span_share import stage_share


def read(ctx):
    return stage_share(ctx, "write", "ec.generate_batch")
