"""Reads what the harness measured itself, on its own clock."""


def read(ctx, args):
    return ctx["harness"].get(args["field"])
