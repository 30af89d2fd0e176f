"""Host seconds of the analysis before any search: trace, NDA and
conflicts (``ToastArtifacts.phase_seconds``)."""


def read(record):
    return sum(record["phase_seconds"].values())
