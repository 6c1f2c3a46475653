"""Line-search trials an L-BFGS iteration in a window of soft trees, by the
program's `lbfgs.passes` less the trees' first evaluations over
`lbfgs.iterations`: a failed search's trials counted, which
`passes_per_iter.gbst` cannot see."""
from pb.trials import trials_per_iteration as read  # noqa: F401
