"""Layer applications a step of a looped decoder, from the program: the
layers its ``loop.plan`` event says one pass runs, times the passes its
own counter over the window holds a reading for (``loop.exit_mass``, a
number a pass run).  Passes x layers of the
configuration when every pass ran; a pass that is skipped shows.  Read and
not judged."""
NAME = "ouro.layer_applications_per_step"


def read(run):
    loop = run.get("loop")
    if not loop or not loop.get("plan"):
        return None
    return loop["plan"]["layers"] * len(loop["exit_mass"])
