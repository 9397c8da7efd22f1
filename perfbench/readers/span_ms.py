"""A percentile of the program's spans named ``span``, each divided by
its own argument ``per`` (a decode burst by its number of steps), in ms."""

from perfbench import arith


def read(obs, args):
    values = [1e3 * s["dur_s"] / float(s["args"].get(args["per"], 1) or 1)
              for s in obs.get("program_spans", ())
              if s["name"] == args["span"]]
    return arith.percentile(values, args["q"]) if values else None
