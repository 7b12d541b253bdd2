"""Memory of the compiled train step of the live world on its fullest
device, from the compiler's memory analysis: arguments + outputs +
temporaries - aliased, in GiB.  It decides whether a job fits."""


def read(run):
    b = run.out.program_bytes
    if not b:
        return None
    return (b["arguments"] + b["outputs"] + b["temporaries"]
            - b["aliased"]) / 2.0 ** 30
