"""Line-search trials an L-BFGS iteration in the window, by the program's
`lbfgs.passes` less its first evaluations over `lbfgs.iterations`: the
program's twin of `passes_per_iter`, which counts passes from outside."""
from pb.trials import trials_per_iteration as read  # noqa: F401
