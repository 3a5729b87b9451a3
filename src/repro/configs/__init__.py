"""Workload configs: `paper_svm` is the paper's own CoCoA+ workload,
imported directly (`from repro.configs.paper_svm import CONFIG`) by the
multi-pod dry run (`repro.launch.dryrun`)."""
