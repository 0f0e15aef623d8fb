"""Command plugin modules — importing registers each with the
COMMANDS registry (the generated style_command.h of the reference)."""

from . import (cc, degree, dump_metrics, dump_plan, dump_trace,  # noqa: F401
               edges, histo, invertedindex, luby, pagerank, rmat, sssp,
               stream, terasort, tpch, tri, wordfreq)
