"""Work completed per second over the whole window: ``obs["work"][of]``
divided by the window's length (all the work, all the time)."""


def read(obs, args):
    return obs["work"][args["of"]] / (obs["t_close"] - obs["t_open"])
