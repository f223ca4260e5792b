from .stream import frame_mesh, make_sharded_frame_decoder  # noqa: F401
