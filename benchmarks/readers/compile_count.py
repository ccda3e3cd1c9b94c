"""XLA compiles between the window's two ends, from the program's
CompileWatch in the chip-owning process. Must be 0."""


def read(evidence, args):
    marks = evidence.get("marks")
    if marks:
        return marks[-1]["compile"]["compiles"] - marks[0]["compile"]["compiles"]
    c = evidence["worker"].get("compile")
    if c and "before" in c:
        return c["after"]["compiles"] - c["before"]["compiles"]
    return None
