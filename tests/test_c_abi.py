"""Linkable C ABI (native/c_api_embed.cpp) — the last unreproduced
interface from VERDICT r3: a real .so a foreign runtime can link, with
the fork driver's call pattern (reference src/test.cpp:243-298:
DatasetCreateFromCSR -> SetField -> BoosterCreate -> UpdateOneIter ->
PredictForCSR, plus Merge/SaveModel/CreateFromModelfile)."""
import shutil
import subprocess
import sys
import sysconfig
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent

pytestmark = pytest.mark.skipif(shutil.which("g++") is None,
                                reason="needs g++")

DRIVER = r"""
#include <cstdint>
#include <cstdio>
#include <string>
#include <unordered_map>
#include <vector>

typedef void* DatasetHandle;
typedef void* BoosterHandle;
extern "C" const char* LGBM_GetLastError();
extern "C" int LGBM_DatasetCreateFromCSR(
    const void*, int, const int32_t*, const void*, int, int64_t,
    int64_t, int64_t, const std::unordered_map<std::string, std::string>,
    const DatasetHandle, DatasetHandle*);
extern "C" int LGBM_DatasetSetField(DatasetHandle, const char*,
                                    const void*, int, int);
extern "C" int LGBM_DatasetGetNumData(DatasetHandle, int*);
extern "C" int LGBM_DatasetFree(DatasetHandle);
extern "C" int LGBM_BoosterCreate(
    const DatasetHandle, std::unordered_map<std::string, std::string>,
    BoosterHandle*);
extern "C" int LGBM_BoosterUpdateOneIter(BoosterHandle, int*);
extern "C" int LGBM_BoosterCalcNumPredict(BoosterHandle, int, int, int,
                                          int64_t*);
extern "C" int LGBM_BoosterPredictForCSR(
    BoosterHandle, const void*, int, const int32_t*, const void*, int,
    int64_t, int64_t, int64_t, int, int,
    std::unordered_map<std::string, std::string>, int64_t*, double*);
extern "C" int LGBM_BoosterSaveModel(BoosterHandle, int, int,
                                     const char*);
extern "C" int LGBM_BoosterCreateFromModelfile(const char*, int*,
                                               BoosterHandle*);
extern "C" int LGBM_BoosterMerge(BoosterHandle, BoosterHandle);
extern "C" int LGBM_BoosterFree(BoosterHandle);

#define CHECK(x) if ((x) != 0) { \
    printf("FAIL %s: %s\n", #x, LGBM_GetLastError()); return 1; }

int main(int argc, char** argv) {
  const int n = 600, f = 4;
  std::vector<int32_t> indptr(n + 1);
  std::vector<int32_t> indices;
  std::vector<double> data;
  std::vector<float> labels(n);
  unsigned s = 12345;
  for (int i = 0; i < n; i++) {
    indptr[i] = (int32_t)indices.size();
    double row0 = 0.0;
    for (int j = 0; j < f; j++) {
      s = s * 1103515245u + 12345u;
      double v = ((s >> 8) % 2000) / 1000.0 - 1.0;
      if (j == 0) row0 = v;
      indices.push_back(j);
      data.push_back(v);
    }
    labels[i] = row0 > 0.0 ? 1.0f : 0.0f;
  }
  indptr[n] = (int32_t)indices.size();

  std::unordered_map<std::string, std::string> params = {
      {"objective", "binary"}, {"num_leaves", "7"},
      {"min_data_in_leaf", "5"}, {"verbose", "-1"}};

  DatasetHandle ds = nullptr;
  CHECK(LGBM_DatasetCreateFromCSR(indptr.data(), 2, indices.data(),
                                  data.data(), 1, n + 1,
                                  (int64_t)data.size(), f, params,
                                  nullptr, &ds));
  CHECK(LGBM_DatasetSetField(ds, "label", labels.data(), n, 0));
  int nd = 0;
  CHECK(LGBM_DatasetGetNumData(ds, &nd));
  if (nd != n) { printf("FAIL num_data %d\n", nd); return 1; }

  BoosterHandle bst = nullptr;
  CHECK(LGBM_BoosterCreate(ds, params, &bst));
  int fin = 0;
  for (int it = 0; it < 8 && !fin; it++) {
    CHECK(LGBM_BoosterUpdateOneIter(bst, &fin));
  }

  int64_t len = 0;
  CHECK(LGBM_BoosterCalcNumPredict(bst, n, 0, -1, &len));
  std::vector<double> preds(len);
  CHECK(LGBM_BoosterPredictForCSR(bst, indptr.data(), 2, indices.data(),
                                  data.data(), 1, n + 1,
                                  (int64_t)data.size(), f, 0, -1,
                                  params, &len, preds.data()));
  int correct = 0;
  for (int i = 0; i < n; i++) {
    correct += ((preds[i] > 0.5) == (labels[i] > 0.5f)) ? 1 : 0;
  }
  if (correct < n * 0.9) { printf("FAIL acc %d/%d\n", correct, n); return 1; }

  std::string model = std::string(argv[1]) + "/model.txt";
  CHECK(LGBM_BoosterSaveModel(bst, 0, -1, model.c_str()));
  int iters = 0;
  BoosterHandle loaded = nullptr;
  CHECK(LGBM_BoosterCreateFromModelfile(model.c_str(), &iters, &loaded));
  std::vector<double> preds2(len);
  CHECK(LGBM_BoosterPredictForCSR(loaded, indptr.data(), 2,
                                  indices.data(), data.data(), 1, n + 1,
                                  (int64_t)data.size(), f, 0, -1,
                                  params, &len, preds2.data()));
  for (int i = 0; i < n; i++) {
    if (preds[i] - preds2[i] > 1e-6 || preds2[i] - preds[i] > 1e-6) {
      printf("FAIL roundtrip row %d: %f vs %f\n", i, preds[i], preds2[i]);
      return 1;
    }
  }
  CHECK(LGBM_BoosterMerge(bst, loaded));
  CHECK(LGBM_BoosterFree(loaded));
  CHECK(LGBM_BoosterFree(bst));
  CHECK(LGBM_DatasetFree(ds));
  printf("C-ABI-OK acc=%d/%d iters=%d\n", correct, n, iters);
  return 0;
}
"""


@pytest.fixture(scope="module")
def capi_so(tmp_path_factory):
    out = tmp_path_factory.mktemp("cabi") / "liblightgbm_tpu.so"
    inc = sysconfig.get_path("include")
    libdir = sysconfig.get_config_var("LIBDIR")
    pyver = f"python{sys.version_info.major}.{sys.version_info.minor}"
    r = subprocess.run(
        ["g++", "-O2", "-shared", "-fPIC", "-std=c++14",
         str(REPO / "native" / "c_api_embed.cpp"), "-o", str(out),
         f"-I{inc}", f"-L{libdir}", f"-l{pyver}", "-ldl", "-lm",
         f"-Wl,-rpath,{libdir}"],
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    return out


def test_fork_driver_flow_links_and_runs(capi_so, tmp_path):
    drv = tmp_path / "driver.cpp"
    drv.write_text(DRIVER)
    exe = tmp_path / "driver"
    r = subprocess.run(
        ["g++", "-O1", "-std=c++14", str(drv), "-o", str(exe),
         f"-L{capi_so.parent}", "-llightgbm_tpu",
         f"-Wl,-rpath,{capi_so.parent}"],
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    import site
    pypath = ":".join([str(REPO)] + site.getsitepackages())
    env = {"PYTHONPATH": pypath, "PATH": "/usr/bin:/bin",
           "JAX_PLATFORMS": "cpu", "HOME": "/tmp"}
    run = subprocess.run([str(exe), str(tmp_path)], env=env,
                         capture_output=True, text=True, timeout=560)
    assert "C-ABI-OK" in run.stdout, (run.stdout, run.stderr)


class TestEmbedGlue:
    """Drive lightgbm_tpu/c_embed.py directly with raw pointers (the
    same marshalling the .so performs) — covers the glue functions the
    C driver doesn't reach."""

    def _mk(self, n=300, f=4):
        r = np.random.default_rng(3)
        X = np.ascontiguousarray(r.normal(size=(n, f)))
        y = (X[:, 0] > 0).astype(np.float32)
        return X, np.ascontiguousarray(y)

    def test_mat_train_eval_refit_save(self, tmp_path):
        from lightgbm_tpu import c_embed as ce

        X, y = self._mk()
        n, f = X.shape
        ds = ce.dataset_from_mat(X.ctypes.data, 1, n, f, 1,
                                 "objective=binary num_leaves=7 "
                                 "metric=auc "
                                 "is_provide_training_metric=true", 0)
        ce.dataset_set_field(ds, "label", y.ctypes.data, n, 0)
        assert ce.dataset_num_data(ds) == n
        assert ce.dataset_num_feature(ds) == f
        bst = ce.booster_create(
            ds, "objective=binary num_leaves=7 metric=auc "
                "is_provide_training_metric=true")
        fin = np.zeros(1, np.int32)
        for _ in range(6):
            ce.booster_update(bst, fin.ctypes.data)
        evals = np.zeros(4, np.float64)
        ne = ce.booster_get_eval(bst, 0, evals.ctypes.data)
        assert ne >= 1 and 0.5 < evals[0] <= 1.0     # train AUC
        # leaf predictions feed refit like the reference's flow
        ln2 = ce.booster_calc_num_predict(bst, n, 2, -1)
        leaves = np.zeros(ln2, np.float64)
        ce.booster_predict_mat(bst, X.ctypes.data, 1, n, f, 1, 2, -1,
                               "", leaves.ctypes.data)
        lp = np.ascontiguousarray(
            leaves.reshape(n, -1).astype(np.int32))
        ce.booster_refit(bst, lp.ctypes.data, n, lp.shape[1])
        # predictions AFTER refit are what the saved model must carry
        ln = ce.booster_calc_num_predict(bst, n, 0, -1)
        out = np.zeros(ln, np.float64)
        got = ce.booster_predict_mat(bst, X.ctypes.data, 1, n, f, 1,
                                     0, -1, "", out.ctypes.data)
        assert got == n
        acc = ((out > 0.5) == y).mean()
        assert acc > 0.85
        mf = str(tmp_path / "m.txt")
        ce.booster_save_model(bst, 0, -1, mf)
        iters = np.zeros(1, np.int32)
        b2 = ce.booster_from_modelfile(mf, iters.ctypes.data)
        assert iters[0] == 6
        out2 = np.zeros(ln, np.float64)
        ce.booster_predict_mat(b2, X.ctypes.data, 1, n, f, 1, 0, -1,
                               "", out2.ctypes.data)
        np.testing.assert_allclose(out, out2, atol=1e-6)
        ce.booster_merge(bst, b2)
        for h in (bst, b2, ds):
            ce.free_handle(h)

    def test_dataset_from_file(self, tmp_path):
        from lightgbm_tpu import c_embed as ce
        X, y = self._mk(200)
        fpath = tmp_path / "d.csv"
        np.savetxt(fpath, np.column_stack([y, X]), delimiter=",")
        ds = ce.dataset_from_file(str(fpath), "objective=binary", 0)
        assert ce.dataset_num_data(ds) == 200
        ce.free_handle(ds)
