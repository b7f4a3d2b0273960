"""Programs the backend compiled anew per call in the window: backend
compiles less persistent-cache hits. The cache keeps only programs that took
JAX's default of a second or more to compile, so a quicker program that a
call re-lowers is compiled again on every call and counts here."""
from chipbench.readers import compiles_per_call as read
