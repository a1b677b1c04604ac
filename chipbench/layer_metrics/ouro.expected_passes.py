"""The pass a token is expected to leave a looped decoder at, by its exit
gates: ``sum_t t x loop.exit_mass_t`` with ``loop.exit_mass_t`` the mean
exit probability of pass ``t`` over the tokens of ALL the window's steps
(the program's own running sum, read at the window's two ends: one step's
reading swings with its batch, whose tokens share most of their stream).
Seeded gates start near (1/2, 1/4, 1/8, 1/8): about 1.9 of 4.  Read and
not judged."""
NAME = "ouro.expected_passes"


def read(run):
    loop = run.get("loop")
    if not loop or not loop.get("exit_mass"):
        return None
    return sum(t * p for t, p in enumerate(loop["exit_mass"], start=1))
