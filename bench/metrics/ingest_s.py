"""Ingest in the run's set-up, on the host clock ended by
`block_until_ready`: `alto.build_device` (linearize, key sort, partition
boxes, fiber counts), `plan.make_plan` and `plan.build_views` (one row
sort per oriented mode)."""


def read(run):
    return run.ingest_s
