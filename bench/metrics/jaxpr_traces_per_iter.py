"""JAX programs traced per iteration of the window.

The sweep runner's grouping, dispatch and block spans (`sweep.group`,
`sweep.dispatch`, `sweep.block`) carry the jaxpr traces made inside them
(`jaxpr_traces`, from `jax.monitoring`); none of them nests in another.
A count: in a window without compiles it repeats for a seed. Nothing is
read where no span carries the count."""


def read(run):
    counts = [sp["args"]["jaxpr_traces"] for sp in run.spans
              if "jaxpr_traces" in sp["args"]]
    return sum(counts) / len(run.window.iterations) if counts else None
