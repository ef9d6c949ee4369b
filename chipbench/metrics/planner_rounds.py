"""Mean communication rounds of the schedules the engine dispatched in the
window: the ``rounds`` arg of each ``engine.offload`` span, which the
program counts from the compiled schedule itself."""


def read(run):
    rounds = [s.args["rounds"] for s in run.spans or ()
              if s.name == "engine.offload" and "rounds" in s.args]
    return sum(rounds) / len(rounds) if rounds else None
