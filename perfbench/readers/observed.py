"""A number the runner observed directly (``setup_s``, the memory peak),
times ``scale``."""


def read(obs, args):
    value = obs.get(args["key"])
    return None if value is None else value * args.get("scale", 1.0)
