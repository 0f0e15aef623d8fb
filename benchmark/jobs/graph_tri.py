"""Job kind ``graph_tri``: the ``oink_script`` job, for the cell that runs
``tri_find``, ``luby_find`` and ``sssp`` over a graph built in set-up.

The job itself is ``oink_script``'s.  This module adds two things that one
has no place for:

* ``prepare`` refuses at once a tree whose ``tri_find`` still walks the
  wedges on the host (no ``jit_tri_wedges`` among the program's names):
  at RMAT-20 that walk is minutes a job with the chip idle, which this
  cell does not start;
* ``info`` names the wedge program and the bytes it must move per job
  (``kernels_tri.wedge_bytes`` over the counts the checked warm-up job
  left on its ``tri.loop`` span), for the ``program_hbm_share`` reader.
"""

from benchmark import check, kernels_tri
from benchmark.jobs import oink_script


class Job(oink_script.Job):
    def prepare(self) -> dict:
        from gpu_mapreduce_tpu.obs import names
        check(hasattr(names, "TRI_WEDGES"),
              "this tree has no device wedge walk (obs/names.py declares no "
              "TRI_WEDGES program): its tri_find enumerates the wedges in "
              "numpy on the host, minutes a job at this scale, which this "
              "cell does not start")
        self.wedge_program = names.TRI_WEDGES
        self.tri_span = names.TRI_ENGINE
        return super().prepare()

    def _walk(self) -> dict:
        """The counts on the newest ``tri.loop`` span in the tracer's ring
        (every job walks the same graph); nothing with the tracer off."""
        from gpu_mapreduce_tpu.obs import get_tracer
        spans = [e for e in get_tracer().events()
                 if e["name"] == self.tri_span]
        return dict(spans[-1]["args"]) if spans else {}

    def check(self, result: dict, outdir: str) -> dict:
        facts = super().check(result, outdir)
        facts["walk"] = self._walk()    # wedges, batches, largest out-degree
        return facts

    def info(self) -> dict:
        w = self._walk()    # read while the ring holds the warm-up job
        if not w:           # the tracer was off: no roofline to report
            return {"programs": {}, "bytes_moved": {}}
        return {"programs": {"tri_wedges": self.wedge_program},
                "bytes_moved": {"tri_wedges": kernels_tri.wedge_bytes(
                    w["wedges"], w["batches"], w["edges"], w["triangles"])}}
