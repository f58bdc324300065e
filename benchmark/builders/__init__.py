"""One module per model family; a configuration's file names its own
under ``builder``.  A module offers ``build(config) -> Workload``."""
