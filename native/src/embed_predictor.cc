// Native predictor over the COMPILED execution path.
//
// Reference parity: inference/api/api_impl.cc:141 NativePaddlePredictor —
// a C++ serving entry point that runs the production engine, not a
// reference interpreter. Here the production engine is the whole-program
// XLA executable (core/lowering.py); this binary embeds CPython (the
// binding route this project uses instead of pybind11) and drives that
// engine in-process: load inference model -> compile once -> execute the
// XLA executable per request. The hand-written f32 interpreter
// (ptpu_demo_predictor) stays as the no-Python fallback.
//
// A direct PJRT C API client would drop the embedded interpreter too; the
// only PJRT plugin shipped on this image is libtpu (hardware the CPU test
// host does not have), so the compiled path binds the engine instead.
//
//   ptpu_compiled_predictor <model_dir> <input.npy> <output.npy>
//                           [feed_name] [fetch_index]
//
// The embedded interpreter resolves imports via PYTHONPATH (point it at
// the repo root and the Python env's site-packages).

#include <Python.h>

#include <cstdio>
#include <cstdlib>
#include <string>

int main(int argc, char** argv) {
  if (argc < 4) {
    std::fprintf(stderr,
                 "usage: %s <model_dir> <input.npy> <output.npy> "
                 "[feed_name] [fetch_index]\n",
                 argv[0]);
    return 2;
  }
  std::string model_dir = argv[1];
  std::string input = argv[2];
  std::string output = argv[3];
  std::string feed = argc > 4 ? argv[4] : "";
  // argv is spliced into generated Python source: the index must be an
  // actual integer and strings must not break out of the r''' literals
  long fetch_index = argc > 5 ? std::strtol(argv[5], nullptr, 10) : 0;
  for (const std::string* s : {&model_dir, &input, &output, &feed}) {
    if (s->find("'''") != std::string::npos ||
        (!s->empty() && (s->back() == '\\' || s->back() == '\''))) {
      std::fprintf(stderr,
                   "argument %s cannot contain ''' or end in a "
                   "backslash or quote\n", s->c_str());
      return 2;
    }
  }

  Py_Initialize();

  std::string script;
  script += "import jax\n";
  script += "jax.config.update('jax_platforms', 'cpu')\n";
  script += "import json, numpy as np\n";
  script += "import paddle_tpu as fluid\n";
  script += "from paddle_tpu.inference import NativeConfig, "
            "create_paddle_predictor\n";
  script += "model_dir = r'''" + model_dir + "'''\n";
  script += "feed = r'''" + feed + "'''\n";
  script += "if not feed:\n";
  script += "    meta = json.load(open(model_dir + '/__meta__.json'))\n";
  script += "    feed = meta['feed_names'][0]\n";
  script += "pred = create_paddle_predictor(\n";
  script += "    NativeConfig(model_dir=model_dir, use_tpu=False))\n";
  std::string idx = std::to_string(fetch_index);
  script += "x = np.load(r'''" + input + "''')\n";
  script += "outs = pred.run({feed: x})\n";
  script += "np.save(r'''" + output + "''', "
            "np.asarray(outs[" + idx + "]))\n";
  script += "print('ok compiled fetch shape',"
            " np.asarray(outs[" + idx + "]).shape)\n";

  int rc = PyRun_SimpleString(script.c_str());
  if (rc != 0) {
    std::fprintf(stderr, "embedded compiled predictor failed\n");
  }
  if (Py_FinalizeEx() < 0 && rc == 0) rc = 1;
  return rc == 0 ? 0 : 1;
}
