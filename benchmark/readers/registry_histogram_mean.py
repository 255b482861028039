"""Mean of what one of the program's histogram families observed over the
window, in the family's own unit (slots, tokens): what
`registry_histogram` reads, without its factor of 1e3 from seconds to
milliseconds."""

import harness


def read(ctx, family):
    ms = harness.load_module("readers", "registry_histogram").read(ctx, family)
    return None if ms is None else ms / 1e3
