"""Seconds of one set-up stage, by the harness's own clock."""


def read(args: dict, ctx: dict):
    return ctx["stage_s"].get(args["stage"])
